"""Readings that the comparison's limits are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 11 12 13 [--control]

For each seed, every batch of the cell's pool goes through the timed
path (the cell's entry, at the cell's size) and through the plain
reference, and both compared numbers are printed per batch.  With
``--control`` the reference computed in the precision below the
configuration's (bfloat16 pair geometry) stands in the program's place
as well: a sound comparison has to fail it.  One JSON line per reading
goes to standard output.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(run.ROOT / "src"))
    import jax.numpy as jnp

    import compare
    import generator
    _, wl, cfg, mix = run.load_cell(args.workload)
    devs = run.chips(wl["chips"])
    run.enable_compile_cache(run.ROOT)
    entry = run.load_module(run.BENCH / "entries" / f"{cfg['entry']}.py")
    ref = run.load_module(run.BENCH / "reference" / f"{cfg['reference']}.py")
    runner = entry.Runner(cfg, devs[:wl["chips"]])
    for seed in args.seeds:
        for i, b in enumerate(generator.make_pool(seed, cfg, mix)):
            rp = run.reference_params(cfg, b)
            row = {"seed": seed, "batch": i}
            t0 = time.perf_counter()
            got = runner.run(b, runner.params(b))
            row["program_s"] = time.perf_counter() - t0
            t0 = time.perf_counter()
            want = ref.Answers(b, rp)
            row["reference_s"] = time.perf_counter() - t0
            row["ties"] = len(want.ties)
            row["program"] = compare.readings(got, want, cfg["limits"])
            if args.control:
                c = ref.reference_labels(b, rp, dtype=jnp.bfloat16)
                row["control"] = compare.readings(
                    [c[f] for f in compare.FIELDS], want, cfg["limits"])
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
