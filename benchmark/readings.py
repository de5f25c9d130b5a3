#!/usr/bin/env python3
"""The readings a ``correct`` limit is set from, at the cell's own size, in
one process (set-up is long): for each seed one whole search through the
runner's own call, the plain reference over the seed's sample, and the
control — the reference put in the program's place and computed in
bfloat16, the nearest precision below the float32 the configuration
states.

    python3 benchmark/readings.py --workload W --seeds 1,2,3 [--control 1]

Prints, per seed, the program's gap to the reference (the lower reading
comes from these) and the control's (the upper reading).  Needs the chip
the cell asks for, like ``run.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]


def every_candidate(path, seed, config, traffic, X, y, splits,
                    reference_fn):
    """Program, reference and control over the whole grid, in blocks of
    the sample's size, so that the distribution of every sample a seed can
    draw is known and not only a dozen draws of it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import check
    import generate
    import run

    new_search, grid = generate.search_factory(config, traffic, seed)
    rec = run.run_search(new_search, X, y)
    candidates = check.candidates_of(grid)
    block = config["check"]["n_candidates"]
    prog = check.split_scores(rec["cv_results"], len(splits))
    ref, ctl = [], []
    t0 = time.perf_counter()
    for i in range(0, len(candidates), block):
        ref.append(reference_fn(X, y, splits, candidates[i:i + block],
                                config)[0])
        ctl.append(reference_fn(X, y, splits, candidates[i:i + block],
                                config, dtype=jnp.bfloat16)[0])
    stats = {str(d.id): d.memory_stats() for d in jax.devices()}
    np.savez(path, C=np.array([c["C"] for c in candidates]), prog=prog,
             ref=np.concatenate(ref), ctl=np.concatenate(ctl))
    print(f"every candidate: {len(candidates)} in "
          f"{time.perf_counter() - t0:.1f} s -> {path}; memory_stats "
          f"{json.dumps(stats)}", flush=True)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=1)
    ap.add_argument("--all", default="",
                    help="write the program's, the reference's and the "
                    "control's split scores of EVERY candidate to this "
                    ".npz file (first seed only)")
    args = ap.parse_args(argv)

    import jax.numpy as jnp

    import check
    import generate
    import run

    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    cell, config, traffic = run.find_cell(bench, args.workload)
    import spark_sklearn_tpu  # noqa: F401
    run.require_chips(cell["chips"])
    from spark_sklearn_tpu.parallel.pipeline import enable_persistent_cache
    enable_persistent_cache()
    reference_fn = run.load_named(config["check"]["reference"])
    cv = generate.load_object(config["cv"]["class"])(**config["cv"]["params"])
    X, y = generate.make_data(config["data"])
    splits = list(cv.split(X, y))
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.all:
        return every_candidate(args.all, seeds[0], config, traffic, X, y,
                               splits, reference_fn)
    for seed in seeds:
        t0 = time.perf_counter()
        new_search, grid = generate.search_factory(config, traffic, seed)
        rec = run.run_search(new_search, X, y)
        run.describe(f"seed {seed} search", rec, len(
            check.candidates_of(grid)) * len(splits))
        t1 = time.perf_counter()
        candidates, sample, reference = check.reference_sample(
            grid, X, y, splits, config, seed, reference_fn)
        t2 = time.perf_counter()
        compared, correct = check.compare(
            [rec["cv_results"]], candidates, sample, reference,
            config["check"])
        out = {"seed": seed, "correct": correct,
               "program": {k: v["value"] for k, v in compared.items()},
               "search_s": round(rec["wall_s"], 3),
               "reference_s": round(t2 - t1, 3)}
        if args.control:
            control = check.reference_sample(
                grid, X, y, splits, config, seed, reference_fn,
                dtype=jnp.bfloat16)[2]
            out["control"] = check.gap_numbers(
                control, reference, check.well_defined(
                    [candidates[i] for i in sample],
                    config["check"].get("well_defined")))
            out["control_s"] = round(time.perf_counter() - t2, 3)
        out["seed_s"] = round(time.perf_counter() - t0, 3)
        print("reading: " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
