"""The join program's share of its HBM roofline, in %: the bytes the
materialize join must move (its inputs read once, the [T, M, T] weight and
index cube written once) at the chip's peak HBM bandwidth, over the join
program's device time per batch.  This is the bandwidth half of the
roofline only: the chip's vector unit has no published peak."""
import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "join_device_ms", Path(__file__).with_name("join.device_ms.py"))
_jd = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_jd)


def join_bytes(T: int, M: int, C: int) -> int:
    """x, y, t (float32) and valid (bool) of every point, the track ids
    (int32), read once; best weight (float32) and best index (int32) of
    every (point, candidate track) written once."""
    return T * M * (3 * 4 + 1) + T * 4 + T * M * C * (4 + 4)


def read(ctx):
    ms = _jd.device_ms(ctx)
    if not ms:
        return None
    T, M = ctx.cfg["n_trajs"], ctx.cfg["max_points"]
    least_s = join_bytes(T, M, T) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
