"""Record ``scoped_tpu_trace.xplane.pb``: one tiny search of the program
under the profiler, on a TPU.

    python3 benchmark/tests/record_scoped_trace.py OUT.xplane.pb

The rehearsal's own configuration (``tiny_logreg.json``, ``tiny_grid.json``;
``max_iter`` cut to 25 to keep the file small), a warm-up search that
builds the programs, then one search inside the runner's ``bench.search``
annotation with the profiler on and python frames off, exactly as
``run.run_window`` traces a cell.  ``test_scopes.py`` reads the file; it
needs no chip.  Exits with 2 where jax's first device is no TPU.
"""

import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]


def main(out):
    import jax

    import generate
    import run
    import scopes
    import trace_reduce

    dev = run.device_block()
    if dev["platform"] != "tpu":
        print(f"a TPU is needed to record a device trace; jax reports {dev}",
              file=sys.stderr)
        return 2
    from spark_sklearn_tpu.parallel.pipeline import enable_persistent_cache
    print("compile cache:", enable_persistent_cache())
    config = run.load_json(os.path.join(HERE, "tiny_logreg.json"))
    config["estimator"]["params"]["max_iter"] = 25
    traffic = run.load_json(os.path.join(HERE, "tiny_grid.json"))
    X, y = generate.make_data(config["data"])
    new_search, _ = generate.search_factory(config, traffic, 2**31 + 26)
    warm = run.run_search(new_search, X, y)
    print("warm-up:", json.dumps(warm["report"]["pipeline"]["n_compiles"]),
          "programs,", warm["report"]["pipeline"]["persistent_cache_hits"],
          "from the compile cache")
    trace_dir = tempfile.mkdtemp()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        run.run_search(new_search, X, y)
    finally:
        jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(trace_dir)
    shutil.copyfile(path, out)
    print(f"{out}: {os.path.getsize(out)} bytes, device {dev}")
    reduced = scopes.reduce(scopes.read_planes(out), 1)
    scopes.describe(reduced, trace_reduce.reduce(
        trace_reduce.load(out), 1)["busy_s"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
