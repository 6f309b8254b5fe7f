"""TSA2's Jaccard kernel's share of its HBM roofline, in %: the bytes it
must move (``tsa2_bytes``) at the chip's peak HBM bandwidth, over
``tsa2.device_ms``.  The bandwidth half of the roofline only: the kernel
is integer work on the vector unit, which has no published peak."""
import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "tsa2_device_ms", Path(__file__).with_name("tsa2.device_ms.py"))
_td = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_td)


def tsa2_bytes(T: int, M: int, C: int) -> int:
    """The ``[T, M, ceil(C / 32)]`` uint32 neighbour words read once and
    the signal ``d [T, M]`` (float32) written once: the kernel takes only
    the words (``window_jaccard`` zeroes those of invalid fixes before
    it)."""
    W = -(-C // 32)
    return T * M * W * 4 + T * M * 4


def read(ctx):
    ms = _td.device_ms(ctx)
    if not ms:
        return None
    T, M = ctx.cfg["n_trajs"], ctx.cfg["max_points"]
    least_s = tsa2_bytes(T, M, T) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
