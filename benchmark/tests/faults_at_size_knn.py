#!/usr/bin/env python3
"""The readings the k-NN cell's ``correct`` limits are set from, at the
cell's OWN size, on the chip the cell asks for, in one process: the plain
reference over EVERY candidate, the control (the reference with the
product in ONE bfloat16 pass, ``reference_knn.py``), one whole search of
the sound program, and the program with each of ``rehearse_knn.py``'s
faults planted.

    python3 benchmark/tests/faults_at_size_knn.py [--seed N] \\
        [--faults own_fold_kept,one_pass] [--out readings.npz]

A fault that changes the program runs its own search after the in-process
program cache is emptied (the cache would hand it the sound programs).  Two
faults are read off the sound search's scores by arithmetic, for every
candidate: ``k_off_by_one`` (a candidate scores what n_neighbors + 1 of its
weighting scores, the next of the grid; the largest stays) and ``distance_ignored`` (a
``distance`` candidate scores its ``uniform`` twin).
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [HERE, BENCH, os.path.dirname(BENCH)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="knn_mnist.k30_weights2")
    ap.add_argument("--seed", type=int, default=2**31 + 41)
    ap.add_argument("--faults", default="own_fold_kept,one_pass")
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import check
    import generate
    import rehearse_knn
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
    new_search, grid = generate.search_factory(config, traffic, args.seed)
    candidates = check.candidates_of(grid)
    region = np.zeros(len(candidates), bool)

    def say(tag, obj):
        print(f"{tag}: " + json.dumps(obj), flush=True)

    def gaps(scores, against):
        return check.gap_numbers(scores, against, region)

    t0 = time.perf_counter()
    ref = reference_fn(X, y, splits, candidates, config)[0]
    t1 = time.perf_counter()
    ctl = reference_fn(X, y, splits, candidates, config,
                       dtype=jnp.bfloat16)[0]
    t2 = time.perf_counter()
    say("reference", {"seconds": round(t1 - t0, 2),
                      "control_seconds": round(t2 - t1, 2),
                      "mean_accuracy_min": float(ref.mean(1).min()),
                      "mean_accuracy_max": float(ref.mean(1).max()),
                      "control": gaps(ctl, ref)})

    def search():
        rec = run.run_search(new_search, X, y)
        rep = rec["report"]
        return rec, check.split_scores(rec["cv_results"], len(splits)), {
            k: rep.get(k) for k in (
                "backend", "n_compile_groups", "n_launches",
                "knn_distance_entries_per_launch",
                "knn_selections_per_launch", "knn_blocks_per_launch",
                "knn_block_rows_per_launch")}

    rec, prog, facts = search()                    # compiles
    rec, prog, facts = search()                    # ... and is timed
    run.describe("sound search", rec, len(candidates) * len(splits))
    from spark_sklearn_tpu.parallel import memledger
    compiled = {k: v for k, v in memledger.get_ledger().snapshot()[
        "compiled"].items() if "kneighbors" in k}
    say("sound", {**facts, "gaps": gaps(prog, ref),
                  "faults": {k: rec["report"].get("faults", {}).get(k)
                             for k in ("retries", "bisections",
                                       "host_fallbacks", "timeouts")},
                  "memory_peak_bytes": run.memory_peak_bytes(),
                  "memory_groups": rec["report"].get("memory", {}).get(
                      "groups"),
                  "compiled": compiled})

    # faults read off the sound scores
    index = {json.dumps(c, sort_keys=True): i
             for i, c in enumerate(candidates)}
    ks = sorted(grid["n_neighbors"])

    def twin(c, **change):
        return index[json.dumps({**c, **change}, sort_keys=True)]

    def next_k(k):       # k + 1 in the cell's grid of every k
        return ks[min(ks.index(k) + 1, len(ks) - 1)]
    off = prog[[twin(c, n_neighbors=next_k(c["n_neighbors"]))
                for c in candidates]]
    uniform = prog[[twin(c, weights="uniform") for c in candidates]]
    say("k_off_by_one", gaps(off, ref))
    say("distance_ignored", gaps(uniform, ref))

    faulty = {}
    for fault in filter(None, args.faults.split(",")):
        from spark_sklearn_tpu.search import grid as grid_mod
        with grid_mod._PROGRAM_CACHE_LOCK:
            grid_mod._PROGRAM_CACHE.clear()
            grid_mod._PROGRAM_CACHE_FAMILY_COUNTS.clear()
        jax.clear_caches()
        unplant = rehearse_knn.plant(fault)
        try:
            rec, scores, _ = search()
        finally:
            unplant()
        faulty[fault] = scores
        say(fault, gaps(scores, ref))
    if args.out:
        np.savez(args.out, ref=ref, ctl=ctl, prog=prog, **faulty)
    return 0


if __name__ == "__main__":
    sys.exit(main())
