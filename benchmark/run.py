#!/usr/bin/env python3
"""python3 benchmark/run.py --workload W --seed N --seconds S --trace 0|1

One run of one cell of ``BENCHMARK.json`` on the chip(s) of the machine
it is started on.  Set-up (imports, data from the seed, programs built or
loaded from the compile cache, warm-up searches), then a closed loop of
whole searches for ``--seconds`` seconds: start a new search while less
than that has elapsed, finish the one in flight, stop.  A search ends when
``cv_results_`` is on the host.

This file knows no cell, configuration, traffic mix or metric by name.
It finds the cell in ``BENCHMARK.json``, the configuration's file there,
the traffic mix at ``traffic/<traffic>.json`` and each per-layer metric's
reader at ``layers/<metric>.py``.  The earlier lines of its output say
what each search did (wall, fits/s, launches, lanes and iterations per
launch, per-launch walls, compiles); the last line is the result.

It exits with 2 and prints no result wherever jax's first device is not a
TPU or the device count is not the cell's ``chips``.  There is no switch
that lets it pass without a chip: the CPU rehearsals in ``tests/`` call
:func:`run_cell` with a tiny configuration of their own.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()   # set-up is counted from here

import argparse      # noqa: E402
import gc            # noqa: E402
import importlib.util  # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import shutil        # noqa: E402
import statistics    # noqa: E402
import sys           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

_FAULT_COUNTERS = ("retries", "bisections", "host_fallbacks", "timeouts")


def say(msg):
    print(msg, flush=True)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_named(spec, base=HERE):
    """``"module:function"`` -> the function of ``<base>/module.py``."""
    module, _, name = spec.partition(":")
    return getattr(load_file(os.path.join(base, module + ".py")), name)


def load_file(path):
    """Import one file by its path; its name may hold dots."""
    mod_name = "bench_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, HERE))
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = module
    spec.loader.exec_module(module)
    return module


def find_cell(bench, name, root=ROOT):
    """The cell's entry, its configuration and its traffic mix."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(has: {sorted(cells)})")
    cell = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(root, entry["file"]))
    traffic = load_json(os.path.join(
        root, bench["paths"][0], "traffic", cell["traffic"] + ".json"))
    return cell, config, traffic


def metrics_of(bench, cell_name, kind):
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------

def device_block():
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes():
    """Peak device memory the run held on the fullest chip, or None where
    the backend reports none (XLA:CPU): the allocator's peak of live
    buffers plus the peak it had reserved for the programs' scratch.  On
    this runtime a launch's temporaries (the class x row x lane tensors of
    the solver) live in that reservation and not in ``bytes_in_use``, and
    it stays reserved between launches; leaving it out would call a cell
    that holds 6 GB of the chip a 0.5 GB cell."""
    import jax
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"])
                         + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks) if peaks else None


def require_chips(chips):
    dev = device_block()
    if dev["platform"] != "tpu" or dev["count"] != chips:
        print(f"this cell needs {chips} TPU chip(s); jax reports {dev}",
              file=sys.stderr, flush=True)
        raise SystemExit(2)
    return dev


# ---------------------------------------------------------------------------
# compile accounting (jax's own monitoring events)
# ---------------------------------------------------------------------------

class CompileClock:
    """Sums jax's compile-phase durations (trace, lower, XLA compile or
    the persistent-cache load that replaced it) and counts the programs
    that went through the back end, so that set-up can say what building
    cost and the window can show that it built nothing."""

    _PHASES = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "backend_compile_s",
        "/jax/compilation_cache/cache_retrieval_time_sec":
            "cache_retrieval_s",
    }

    def __init__(self):
        from jax._src import monitoring
        self.totals = {v: 0.0 for v in self._PHASES.values()}
        self.totals["programs"] = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event, duration, **kwargs):
        key = self._PHASES.get(event)
        if key is not None:
            self.totals[key] += float(duration)
            if key == "backend_compile_s":
                self.totals["programs"] += 1

    def snapshot(self):
        return dict(self.totals)

    @staticmethod
    def delta(before, after):
        d = {k: after[k] - before[k] for k in after}
        d["compile_s"] = (d["trace_s"] + d["lower_s"]
                          + d["backend_compile_s"] + d["cache_retrieval_s"])
        return d


# ---------------------------------------------------------------------------
# searches
# ---------------------------------------------------------------------------

def run_search(new_search, X, y):
    """One whole search, from the call to ``cv_results_`` on the host."""
    import jax
    import numpy as np

    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.search"):
        search = new_search().fit(X, y)
        results = {k: np.asarray(v) if k != "params" else list(v)
                   for k, v in search.cv_results_.items()}
    wall = time.perf_counter() - t0
    return {"wall_s": wall, "cv_results": results,
            "report": search.search_report}


def describe(tag, rec, fits):
    rep = rec["report"]
    launches = rep.get("pipeline", {}).get("launches", [])
    say(f"{tag}: " + json.dumps({
        "wall_s": round(rec["wall_s"], 4),
        "fits_per_s": round(fits / rec["wall_s"], 2),
        "n_launches": rep.get("pipeline", {}).get("n_launches"),
        "n_compiles": rep.get("pipeline", {}).get("n_compiles"),
        "lanes_per_launch": rep.get("lanes_per_launch"),
        "solver_iters_per_launch": rep.get("solver_iters_per_launch"),
        "launch_compute_s": [round(t.get("compute_s", 0.0), 4)
                             for t in launches],
        "faults": {k: rep.get("faults", {}).get(k, 0)
                   for k in _FAULT_COUNTERS},
    }))


def run_window(new_search, X, y, seconds, fits, trace_dir=None):
    """The closed loop.  With ``trace_dir`` the profiler traces the first
    search of the window only (traces are large); starting it and writing
    the trace out are the harness's own work and stay out of the window."""
    import jax

    records, untimed = [], 0.0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 - untimed < seconds:
        if trace_dir and not records:
            t = time.perf_counter()
            # no python frames: they slow the host and name no jax call
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            untimed += time.perf_counter() - t
            try:
                records.append(run_search(new_search, X, y))
            finally:
                t = time.perf_counter()
                jax.profiler.stop_trace()
                untimed += time.perf_counter() - t
        else:
            records.append(run_search(new_search, X, y))
    window_s = time.perf_counter() - t0 - untimed
    for i, rec in enumerate(records):
        describe(f"search {i}", rec, fits)
    return records, window_s


# ---------------------------------------------------------------------------
# one run of one cell
# ---------------------------------------------------------------------------

def run_cell(bench, cell_name, seed, seconds, trace, root=ROOT, t0=None,
             trace_dir=None):
    """Everything but the demand for a chip.  Returns the result line's
    object."""
    import jax
    import numpy as np

    import check
    import generate
    import trace_reduce
    import work

    t0 = time.perf_counter() if t0 is None else t0
    cell, config, traffic = find_cell(bench, cell_name, root)
    bench_dir = os.path.join(root, bench["paths"][0])
    dev = device_block()

    # the program's own resolver places the compile cache: the environment
    # wins, else the fixed directory inside the checkout
    from spark_sklearn_tpu.parallel.pipeline import enable_persistent_cache
    say(f"compile cache: {enable_persistent_cache()}")
    clock = CompileClock()

    t_data = time.perf_counter()
    X, y = generate.make_data(config["data"])
    new_search, grid = generate.search_factory(config, traffic, seed)
    cv = generate.load_object(config["cv"]["class"])(**config["cv"]["params"])
    splits = list(cv.split(X, y))
    fits = len(check.candidates_of(grid)) * len(splits)
    say(f"cell {cell_name}: X{X.shape} {X.dtype}, {fits} fits per search, "
        f"data in {time.perf_counter() - t_data:.2f} s, "
        f"{time.perf_counter() - t0:.2f} s since the start")

    # warm-up: one search (call 2 of PR 25: the window after a compiling
    # warm-up equals a cached run's to 2 ms a search, so one is enough)
    c0 = clock.snapshot()
    warm = run_search(new_search, X, y)
    describe("warm-up", warm, fits)
    setup = CompileClock.delta(c0, clock.snapshot())
    del warm
    gc.collect()
    setup_s = time.perf_counter() - t0

    w0 = clock.snapshot()
    if trace:
        trace_dir = trace_dir or os.path.join(root, ".bench_trace",
                                              cell_name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    records, window_s = run_window(new_search, X, y, seconds, fits,
                                   trace_dir if trace else None)
    in_window = CompileClock.delta(w0, clock.snapshot())
    peak_bytes = memory_peak_bytes()
    say("memory_stats: " + json.dumps(
        {str(d.id): d.memory_stats() for d in jax.devices()}))
    n = len(records)
    walls = [r["wall_s"] for r in records]
    say(f"window: {window_s:.4f} s, {n} searches, search_wall_s "
        f"{window_s / n:.4f}, fits/s {fits * n / window_s:.2f}, "
        f"median search {statistics.median(walls):.4f} s, programs built "
        f"in window {in_window['programs']}")

    all_cv = [r["cv_results"] for r in records]
    failed = sum(check.failed_fits(cv_res, len(splits)) for cv_res in all_cv)

    device = dict(dev)
    device["memory_peak_bytes"] = peak_bytes
    result_metrics = {}
    breakdown = None
    if trace:
        reduced = None
        path = trace_reduce.find_xplane(trace_dir)
        if path is not None:
            reduced = trace_reduce.reduce(trace_reduce.load(path),
                                          dev["count"])
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
        ctx = {
            "config": config, "traffic": traffic, "cell": cell,
            "device": device, "chips": dev["count"],
            "fits_per_search": fits,
            "n_candidates": fits // len(splits),
            "reports": [r["report"] for r in records],
            "report": records[0]["report"],
            "search_walls": walls, "window_s": window_s,
            "setup_compile": setup, "window_compile": in_window,
            "trace": reduced, "work": work,
            "load_named": lambda spec: load_named(spec, bench_dir),
        }
        for m in metrics_of(bench, cell_name, "per_layer"):
            reader = load_file(os.path.join(
                bench_dir, "layers", m["name"] + ".py"))
            value = reader.read(ctx)
            if value is not None:
                result_metrics[m["name"]] = {"value": float(value),
                                             "unit": m["unit"]}
    else:
        values = {"search_wall_s": window_s / n, "setup_s": setup_s}
        for m in metrics_of(bench, cell_name, "end_to_end"):
            result_metrics[m["name"]] = {"value": values[m["name"]],
                                         "unit": m["unit"]}

    # the comparison runs last: the window is closed, the peak is read
    del records
    gc.collect()
    t_check = time.perf_counter()
    reference_fn = load_named(config["check"]["reference"], bench_dir)
    candidates, sample, reference = check.reference_sample(
        grid, X, y, splits, config, seed, reference_fn)
    compared, correct = check.compare(
        all_cv, candidates, sample, reference, config["check"])
    say(f"comparison with the reference: {time.perf_counter() - t_check:.2f}"
        f" s, whole run {time.perf_counter() - t0:.2f} s")

    result = {"correct": correct, "attempted": fits * n, "failed": failed,
              "metrics": result_metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    check.print_compared(compared, correct)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, _, _ = find_cell(bench, args.workload)
    import spark_sklearn_tpu  # noqa: F401 — no program, no run
    t_import = time.perf_counter()
    require_chips(cell["chips"])
    say(f"imports in {t_import - _T0:.2f} s, devices in "
        f"{time.perf_counter() - t_import:.2f} s")
    result = run_cell(bench, args.workload, args.seed, args.seconds,
                      bool(args.trace), t0=_T0)
    sys.stderr.flush()
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
