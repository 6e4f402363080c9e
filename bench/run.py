"""Run one benchmark cell on the chips of this machine and print its result.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about the cell is data found by name: the cell in
``BENCHMARK.json``, its configuration file, its traffic mix
(``bench/traffic/<traffic>.json``), the driver the mix names
(``bench/drivers/<driver>.py``) and one reader per per-layer metric
(``bench/metrics/<metric>.py``).

One process: check the platform, set up (weights, warm-up of every shape the
traffic uses), measure for ``--seconds`` (whole waves of requests, so the
window can run past it), read the peak device memory, free the program's
state, compare what the window produced with the plain reference, and print
one JSON line.  With ``--trace 0`` the line carries the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the profiler and the line
carries the per-layer metrics, the device's busy time and a breakdown.
Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
before any model work and prints no result.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
TRACE_DIR = BENCH / ".trace"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell_spec(name: str) -> dict:
    """The cell, its configuration and its traffic mix, by name."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return {
        "bench": bench, "cell": cell,
        "config": json.loads((ROOT / conf["file"]).read_text()),
        "mix": json.loads(
            (BENCH / "traffic" / f"{cell['traffic']}.json").read_text()),
    }


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class CompileCounter:
    """Compilations and persistent-cache loads, counted per phase."""

    def __init__(self):
        import jax
        self.phase = "setup"
        self.counts = {}
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _bump(self, what):
        row = self.counts.setdefault(self.phase, {"compiles": 0,
                                                  "cache_loads": 0})
        row[what] += 1

    def _dur(self, event, secs, **_):
        if event == _COMPILE_EVENT:
            self._bump("compiles")

    def _event(self, event, **_):
        if event == _CACHE_HIT:
            self._bump("cache_loads")


class GcWatch:
    """Python's garbage collections from construction to ``stop()``: how
    many of each generation, and the longest pause."""

    def __init__(self):
        self.count = [0, 0, 0]
        self.longest = 0.0
        self._t0 = 0.0
        gc.callbacks.append(self._cb)

    def stop(self):
        gc.callbacks.remove(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.count[info["generation"]] += 1
            self.longest = max(self.longest, time.perf_counter() - self._t0)


def device_check(chips: int):
    """The platform first: a TPU with at least ``chips`` devices, or exit."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        raise SystemExit(2)
    return devs


def peak_bytes(devs) -> int:
    return max(int(d.memory_stats().get("peak_bytes_in_use", 0))
               for d in devs)


def traced_window(driver, seconds: float):
    """The window under the profiler, without the Python tracer (it would
    slow the host loop it measures); returns (window result, trace)."""
    import jax

    from bench import trace as T
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
            res = driver.window(seconds)
    finally:
        jax.profiler.stop_trace()
    path = T.find_file(str(TRACE_DIR))
    return res, (T.load(path) if path else None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be non-negative")

    spec = cell_spec(args.workload)
    cell = spec["cell"]
    devs = device_check(cell["chips"])

    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # cache every program, small ones too, so later runs compile nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    counter = CompileCounter()

    drivers = BENCH / "drivers"
    driver = load_module(drivers / f"{spec['mix']['driver']}.py").Driver(
        spec["config"], spec["mix"], args.seed, chips=cell["chips"])
    with jax.profiler.TraceAnnotation("bench.setup"):
        driver.setup()
    setup_s = time.time() - T_START

    counter.phase = "window"
    watch = GcWatch()
    t0 = time.time()
    if args.trace:
        res, tr = traced_window(driver, args.seconds)
    else:
        res, tr = driver.window(args.seconds), None
    t_window = time.time() - t0
    watch.stop()
    counter.phase = "check"
    mem = peak_bytes(devs[:cell["chips"]])
    driver.release()
    t0 = time.time()
    correct, checks, failed = driver.check(res)
    t_check = time.time() - t0

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    bench = spec["bench"]
    metrics = {}
    breakdown = None
    if not args.trace:
        values = dict(driver.end_to_end(res), setup_s=setup_s)
        for m in bench["end_to_end"]:
            if applies(m, cell["name"]) and m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        from bench import trace as T
        from bench.peaks import peaks
        ctx = driver.layer_context(res, tr, peaks(devs[0].device_kind))
        for m in bench["per_layer"]:
            if not applies(m, cell["name"]):
                continue
            v = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if tr is not None and tr.window() is not None and tr.device_ops:
            lo, hi = tr.window()
            planes = sorted(tr.device_ops)[:cell["chips"]]
            device["busy_s"] = sum(T.busy_ns(tr.busy_ops(p), lo, hi)
                                   for p in planes) / len(planes) * 1e-9
            device["window_s"] = (hi - lo) * 1e-9
            breakdown = {
                "device_ops": T.top_ops(tr.device_ops[planes[0]], lo, hi),
                "idle_gaps": T.idle_gaps(tr, tr.busy_ops(planes[0]), lo, hi)}

    print(f"[phases] setup {setup_s:.3f}s, window (with the trace read) "
          f"{t_window:.3f}s, check {t_check:.3f}s, whole run "
          f"{time.time() - T_START:.3f}s; compiles/cache loads per phase "
          f"{counter.counts}", file=sys.stderr)
    print(f"[gc] in the window {watch.count} collections "
          f"(generations 0/1/2), longest {watch.longest:.3f}s",
          file=sys.stderr)
    for name, c in checks.items():
        print(f"[check] {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    out = {"correct": bool(correct), "attempted": res.attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
