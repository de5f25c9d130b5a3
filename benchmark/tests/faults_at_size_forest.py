#!/usr/bin/env python3
"""The readings the forest cell's ``correct`` limits are set from, at the
cell's OWN size, on the chip the cell asks for: for one whole search what
``correct`` compares, with one of ``rehearse_forest.py``'s faults planted
underneath it (or with none), against the plain reference over EVERY
candidate, and the control's reading (the reference with cumulative sums
and gains rounded to bfloat16).

    python3 benchmark/tests/faults_at_size_forest.py --reference R.npz \\
        [--fault NAME] [--control 1] [--depths 8] [--seed N]

One process a fault that changes the program: the program store would hand
a second search of the same process the sound programs.  The reference's
and the control's split scores (one row a candidate of the UNPERMUTED
grid; they do not depend on the seed, which only orders the grid) are kept
in ``R.npz`` by the first call and read by the others.  ``--depths`` cuts
the grid to some of its depths: a fault's reading needs one compile group,
not three (a program compiles for a minute and more).  The faults that
only move scores (``swapped_scores``, ``altered_score``,
``n_estimators_ignored``) are read off the sound search's scores, by
arithmetic, for every pair or cell they could hit.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [HERE, BENCH, os.path.dirname(BENCH)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload",
                    default="forest_covtype145k.depth3_trees3")
    ap.add_argument("--seed", type=int, default=2**31 + 35)
    ap.add_argument("--fault")
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--depths", default="")
    ap.add_argument("--reference", required=True)
    args = ap.parse_args()

    import jax.numpy as jnp
    import numpy as np

    import check
    import generate
    import rehearse_forest
    import run

    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    cell, config, traffic = run.find_cell(bench, args.workload)
    import spark_sklearn_tpu  # noqa: F401
    run.require_chips(cell["chips"])
    from spark_sklearn_tpu.parallel.pipeline import enable_persistent_cache
    enable_persistent_cache()
    if args.depths:
        traffic["param_grid"]["max_depth"] = [
            int(d) for d in args.depths.split(",")]
    cv = generate.load_object(config["cv"]["class"])(**config["cv"]["params"])
    X, y = generate.make_data(config["data"])
    splits = list(cv.split(X, y))
    new_search, grid = generate.search_factory(config, traffic, args.seed)
    candidates = check.candidates_of(grid)
    spec = config["check"]
    reference_fn = run.load_named(spec["reference"])

    # every candidate, keyed by its values: the file serves every seed
    key = lambda c: json.dumps(c, sort_keys=True)
    kept = dict(np.load(args.reference)) if os.path.exists(
        args.reference) else {}
    for name, kwargs in (("ref", {}), ("ctl", {"dtype": jnp.bfloat16})):
        missing = [c for c in candidates if f"{name}:{key(c)}" not in kept]
        if missing and (name == "ref" or args.control):
            scores = reference_fn(X, y, splits, missing, config, **kwargs)[0]
            kept.update({f"{name}:{key(c)}": s
                         for c, s in zip(missing, scores)})
            np.savez(args.reference, **kept)
    reference = np.stack([kept[f"ref:{key(c)}"] for c in candidates])
    sample = np.arange(len(candidates))
    region = check.well_defined(candidates, spec.get("well_defined"))

    if args.fault in rehearse_forest.PROGRAM_FAULTS:
        rehearse_forest.plant(args.fault)
    rec = run.run_search(new_search, X, y)
    run.describe("search", rec, len(candidates) * len(splits))
    scores = check.split_scores(rec["cv_results"], len(splits))
    out = {"fault": args.fault, "search_s": round(rec["wall_s"], 3),
           "reference_mean": reference.mean(axis=1).round(4).tolist(),
           "program": check.gap_numbers(scores, reference, region),
           "per_candidate_mean_gap": (scores - reference).mean(
               axis=1).round(5).tolist()}
    if args.control and all(f"ctl:{key(c)}" in kept for c in candidates):
        control = np.stack([kept[f"ctl:{key(c)}"] for c in candidates])
        out["control"] = check.gap_numbers(control, reference, region)
    if not args.fault:
        # the faults that only move scores, from the sound scores: the
        # least each reads over every place it could hit
        n = len(candidates)
        swaps = [check.gap_numbers(_swapped(scores, i, j), reference, region)
                 for i in range(n) for j in range(i + 1, n)]
        out["swapped_scores_least"] = _least(swaps)
        by = {}
        for i, c in enumerate(candidates):
            by.setdefault(c["max_depth"], []).append(i)
        ignored = scores.copy()
        for rows in by.values():
            most = max(rows, key=lambda i: candidates[i]["n_estimators"])
            ignored[rows] = scores[most]
        out["n_estimators_ignored"] = check.gap_numbers(
            ignored, reference, region)
    print("fault reading: " + json.dumps(out), flush=True)


def _swapped(scores, i, j):
    out = scores.copy()
    out[[i, j]] = out[[j, i]]
    return out


def _least(numbers):
    return {k: min(n[k] for n in numbers if k in n) for k in numbers[0]}


if __name__ == "__main__":
    main()
