"""Run one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name from ``BENCHMARK.json``:
the configuration ``configs/<config>.json`` (sizes, plan, the entry into
the program under ``entries/``, its plain reference under ``reference/``
and the comparison's limits), the traffic mix ``traffic/<traffic>.json``
(read by ``generator.py``) and each per-layer metric
``metrics/<metric>.py`` (a ``read(ctx)`` that returns a number or None).

A run: set-up (imports, device start, the batch pool generated from the
seed, every pool batch run once so that each program is compiled or
loaded from the compile cache in ``.jax_cache/``), then batches back to
back for ``--seconds``, each from host arrays to labels ready on the
device.  ``--trace 0`` reports the cell's end-to-end metrics, ``--trace
1`` traces the window and reports its per-layer metrics.  After the
window every answer is compared with the reference; the last line of
standard output is one JSON object.  Without a TPU, or with fewer chips
than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import compare  # noqa: E402
import generator  # noqa: E402
import trace_reduce  # noqa: E402
from peaks import peaks_for  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    pass


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str):
    """``(benchmark, workload, config, mix)`` of the cell ``name``."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    wl = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    cfg = json.loads((ROOT / cfg_entry["file"]).read_text())
    mix = generator.load_mix(BENCH / "traffic" / f"{wl['traffic']}.json")
    return bench, wl, cfg, mix


class CompileCounter:
    """JAX's own compile events (a persistent-cache load is one too), with
    the host time at which each ended."""

    def __init__(self):
        from jax import monitoring
        self.compiles, self.cache_hits = [], []
        self._m = monitoring
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.compiles.append((time.perf_counter(), duration))

    def _on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits.append(time.perf_counter())

    def between(self, t0, t1) -> int:
        return sum(t0 <= t <= t1 for t, _ in self.compiles)

    def close(self):
        self._m.unregister_event_duration_listener(self._on_duration)
        self._m.unregister_event_listener(self._on_event)


def enable_compile_cache(root: Path):
    """The persistent compilation cache at a fixed path in the checkout,
    every program in it, whatever the environment names."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chips(n: int, platform: str = "tpu"):
    import jax
    devs = jax.devices()
    if devs[0].platform != platform:
        raise NoChip(f"JAX found no {platform} (platform "
                     f"{devs[0].platform!r})")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX found {len(devs)}")
    return devs


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             platform: str = "tpu", cfg_override=None, mix_override=None,
             runner_wrap=None, log=print) -> dict:
    """One run of ``workload``; returns the result object.

    ``platform``, ``cfg_override`` and ``mix_override`` (the configuration
    and the traffic mix, in place of the cell's) and ``runner_wrap`` (a
    function of the runner that returns the runner the window drives) let
    the tests drive a small run on the CPU with a fault planted under the
    timed path."""
    bench, wl, cfg, mix = load_cell(workload)
    cfg = {**cfg, **(cfg_override or {})}
    mix = mix_override or mix
    import jax
    devs = chips(wl["chips"], platform)
    counter = CompileCounter()
    try:
        return _run(bench, wl, cfg, mix, devs, seed, seconds, trace,
                    counter, runner_wrap, log)
    finally:
        counter.close()
        jax.clear_caches()


def _run(bench, wl, cfg, mix, devs, seed, seconds, trace, counter,
         runner_wrap, log):
    import jax
    entry = load_module(BENCH / "entries" / f"{cfg['entry']}.py")
    reference = load_module(BENCH / "reference" / f"{cfg['reference']}.py")
    used = devs[:wl["chips"]]
    pool = generator.make_pool(seed, cfg, mix)
    runner = entry.Runner(cfg, used)
    if runner_wrap is not None:
        runner = runner_wrap(runner)
    params = [runner.params(b) for b in pool]
    for b, p in zip(pool, params):      # every program the window runs
        runner.run(b, p)
    setup_s = time.perf_counter() - PROCESS_START
    log(f"set-up {setup_s:.3f} s, {len(counter.compiles)} compiles "
        f"({len(counter.cache_hits)} from the cache)", file=sys.stderr)

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    counters = {}
    # a traced run also keeps what the entry's counters see in the window
    watch = getattr(runner, "watch", None) if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    answers = []
    with (watch(counters) if watch else contextlib.nullcontext()), \
            jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        t0 = time.perf_counter()
        while True:
            i = len(answers) % len(pool)
            answers.append((i, runner.run(pool[i], params[i])))
            t1 = time.perf_counter()
            if t1 - t0 >= seconds:
                break
    if trace:
        jax.profiler.stop_trace()
    compiles = counter.between(t0, t1)
    memory_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use",
                                                        0)) for d in used)
    window_s = t1 - t0
    points = sum(pool[i].n_points for i, _ in answers)
    log(f"window {window_s:.3f} s: {len(answers)} batches, {points} points, "
        f"{compiles} compiles", file=sys.stderr)

    # the reference runs once the window has closed and its state is freed
    answers = [(i, [jax.device_get(a) for a in lab]) for i, lab in answers]
    del runner, watch
    per_answer, want = [], {}
    ref_t0 = time.perf_counter()
    for i, got in answers:
        if i not in want:
            want[i] = reference.Answers(pool[i],
                                        reference_params(cfg, pool[i]))
        per_answer.append(compare.readings(got, want[i], cfg["limits"]))
    log(f"reference {time.perf_counter() - ref_t0:.3f} s for {len(want)} "
        f"batches; decisions within {cfg['tie_margin']:g} of a tie: "
        f"{[len(a.ties) for a in want.values()]}, alternatives looked at: "
        f"{max(r['alternatives'] for r in per_answer)}", file=sys.stderr)
    failed, checks = compare.judge(per_answer, cfg["limits"])

    dev = used[0]
    result = {"correct": failed == 0, "attempted": len(answers),
              "failed": failed, "metrics": {},
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devs),
                         "memory_peak_bytes": memory_peak}}
    if not trace:
        e2e = {"points_per_s": points / window_s, "setup_s": setup_s}
        for m in bench["end_to_end"]:
            if wl["name"] in m.get("workloads", [wl["name"]]):
                result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                "unit": m["unit"]}
    else:
        tr = trace_reduce.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        # what a per-layer metric's read(ctx) may look at
        ctx = SimpleNamespace(trace=tr, n_batches=len(answers),
                              compiles=compiles, counters=counters, cfg=cfg,
                              chips=len(used),
                              peaks=peaks_for(dev.device_kind))
        for m in bench["per_layer"]:
            if wl["name"] not in m.get("workloads", [wl["name"]]):
                continue
            v = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        busy = [trace_reduce.busy_ns(tr, d) for d in tr.ops]
        log("busy share per chip: " + ", ".join(
            f"{d}: {b / tr.window_ns:.4f}" for d, b in zip(tr.ops, busy)),
            file=sys.stderr)
        result["device"]["busy_s"] = sum(busy) / len(busy) / 1e9
        result["device"]["window_s"] = tr.window_ns / 1e9
        result["breakdown"] = trace_reduce.breakdown(tr)
    result["checks"] = checks
    return result


def reference_params(cfg: dict, batch) -> dict:
    return {"eps_sp": batch.eps_sp, "eps_t": batch.eps_t, "w": cfg["w"],
            "tau": cfg["tau"], "alpha_sigma": cfg["alpha_sigma"],
            "k_sigma": cfg["k_sigma"], "max_subs": cfg["max_subtrajs"],
            "tie_margin": cfg["tie_margin"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    enable_compile_cache(ROOT)
    try:
        res = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in res["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
