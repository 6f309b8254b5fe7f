"""The refined join kernel's share of its HBM roofline, in %: the bytes of
the materialize join (``join_roofline.join_bytes``: its inputs read once,
the [T, M, T] weight and index cube written once) at the chip's peak HBM
bandwidth, over ``join_dtj.device_ms``.  The bandwidth half of the
roofline only, as ``join_roofline``."""
import importlib.util
from pathlib import Path


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), Path(__file__).with_name(f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_jd = _load("join_dtj.device_ms")
_roof = _load("join_roofline")


def read(ctx):
    ms = _jd.device_ms(ctx)
    if not ms:
        return None
    T, M = ctx.cfg["n_trajs"], ctx.cfg["max_points"]
    least_s = _roof.join_bytes(T, M, T) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
