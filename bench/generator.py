"""The benchmark's one traffic generator: batches of lane-following tracks.

A traffic mix is a data file, ``traffic/<mix>.json``, of the parameters
below; the cell's configuration gives the batch shape (``n_trajs`` tracks
of at most ``max_points`` fixes).  The mix's ``pool`` batches are drawn
from seeds derived from the run's ``--seed``, so the same seed gives the
same inputs.

Per track: a lane between two uniform waypoints, a per-vessel offset
from it and speed along it, ``fixes_min`` to ``fixes_max`` fixes at
``sample_dt`` seconds with ``dt_jitter``, and a first fix uniform over
the batch's time span, which is ``n_trajs / tracks_per_day`` days.  Rows
are in first-fix order, as tracks cut from a time-ordered feed come.
The lane model is the one of the program's ``ais_like`` generator,
written out here so that the yardstick does not move with the program.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

MIX_KEYS = ("n_lanes", "area", "mean_speed", "sample_dt", "dt_jitter",
            "lane_width", "fixes_min", "fixes_max", "tracks_per_day", "pool")
DAY_S = 86_400.0


@dataclass
class Batch:
    """One generated batch: host arrays plus the DSC parameters derived
    from it (the launcher's rule: ``eps_sp`` a share of the diameter,
    ``eps_t`` a multiple of the mean sampling interval)."""
    x: np.ndarray        # [T, M] float32
    y: np.ndarray        # [T, M] float32
    t: np.ndarray        # [T, M] float32, ascending within a row
    valid: np.ndarray    # [T, M] bool, a prefix of each row
    traj_id: np.ndarray  # [T] int32
    eps_sp: float
    eps_t: float

    @property
    def n_points(self) -> int:
        return int(self.valid.sum())


def load_mix(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    missing = [k for k in MIX_KEYS if k not in mix]
    if missing:
        raise ValueError(f"{path}: traffic mix lacks {missing}")
    return mix


def lane_tracks(rng: np.random.Generator, n_trajs: int, max_points: int,
                mix: dict) -> list[np.ndarray]:
    """``n_trajs`` tracks as ``[n_i, 3]`` (x, y, t) arrays in first-fix
    order, ``n_i`` uniform in ``[fixes_min, fixes_max]``."""
    if mix["fixes_max"] > max_points:
        raise ValueError(f"fixes_max {mix['fixes_max']} > max_points "
                         f"{max_points}")
    area = mix["area"]
    lanes = rng.uniform(0.1 * area, 0.9 * area, (mix["n_lanes"], 2, 2))
    span = DAY_S * n_trajs / mix["tracks_per_day"]
    tracks = []
    for _ in range(n_trajs):
        lane = int(rng.integers(mix["n_lanes"]))
        p0, p1 = lanes[lane]
        direction = (p1 - p0) / (np.linalg.norm(p1 - p0) + 1e-9)
        offset = rng.normal(0.0, mix["lane_width"], 2)
        speed = mix["mean_speed"] * rng.uniform(0.7, 1.3)
        n = int(rng.integers(mix["fixes_min"], mix["fixes_max"] + 1))
        t0 = rng.uniform(0.0, span)
        dts = mix["sample_dt"] * rng.uniform(
            1.0 - mix["dt_jitter"], 1.0 + mix["dt_jitter"], n)
        t = t0 + np.cumsum(dts)
        s = np.minimum(speed * (t - t[0]), np.linalg.norm(p1 - p0))
        pts = p0[None, :] + offset[None, :] + s[:, None] * direction[None, :]
        pts = pts + rng.normal(0.0, 0.05 * mix["lane_width"], pts.shape)
        tracks.append(np.concatenate([pts, t[:, None]], axis=1))
    tracks.sort(key=lambda tr: float(tr[0, 2]))
    return tracks


def pack(tracks: list[np.ndarray], max_points: int):
    """Pad tracks to ``[T, M]`` float32 rows, time-sorted, valid prefix."""
    T = len(tracks)
    x = np.zeros((T, max_points), np.float32)
    y = np.zeros((T, max_points), np.float32)
    t = np.zeros((T, max_points), np.float32)
    valid = np.zeros((T, max_points), bool)
    for i, tr in enumerate(tracks):
        tr = np.asarray(tr, np.float32)
        tr = tr[np.argsort(tr[:, 2], kind="stable")][:max_points]
        m = len(tr)
        x[i, :m], y[i, :m], t[i, :m] = tr[:, 0], tr[:, 1], tr[:, 2]
        valid[i, :m] = True
    return x, y, t, valid, np.arange(T, dtype=np.int32)


def derived_params(x, y, t, valid, cfg: dict) -> tuple[float, float]:
    """``(eps_sp, eps_t)``: the configuration's share of the batch's
    spatial diameter, and its multiple of the mean sampling interval
    (mean over tracks of each track's mean gap)."""
    xv, yv = x[valid], y[valid]
    diam = float(np.hypot(xv.max() - xv.min(), yv.max() - yv.min()))
    n = valid.sum(1)
    gaps = np.where(valid[:, 1:], np.diff(t, axis=1), 0.0)
    has = n > 1
    mean_dt = float(np.mean(gaps[has].sum(1) / (n[has] - 1)))
    return cfg["eps_sp_of_diameter"] * diam, cfg["eps_t_of_mean_dt"] * mean_dt


def make_batch(rng: np.random.Generator, cfg: dict, mix: dict) -> Batch:
    tracks = lane_tracks(rng, cfg["n_trajs"], cfg["max_points"], mix)
    x, y, t, valid, ids = pack(tracks, cfg["max_points"])
    eps_sp, eps_t = derived_params(x, y, t, valid, cfg)
    return Batch(x, y, t, valid, ids, eps_sp, eps_t)


def make_pool(seed: int, cfg: dict, mix: dict) -> list[Batch]:
    """The mix's pool of batches; batch ``i`` draws from ``[seed, i]``."""
    return [make_batch(np.random.default_rng([seed, i]), cfg, mix)
            for i in range(mix["pool"])]
