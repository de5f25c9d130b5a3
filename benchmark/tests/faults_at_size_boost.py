#!/usr/bin/env python3
"""The readings the boosting cell's ``correct`` limits are set from, at the
cell's OWN size, on the chip the cell asks for: for one whole search what
``correct`` compares, with one of ``rehearse_boost.py``'s faults planted
underneath it (or with none), against the plain reference over EVERY
candidate.

    python3 benchmark/tests/faults_at_size_boost.py --reference R.npz \\
        [--fault NAME] [--seed N]

The control is ``--fault one_part``: the same search with a row's
statistics in ONE bfloat16 part, the precision below the configuration's
three.  One process a fault that changes the program: the program store
would hand a second search of the same process the sound programs.  The
reference's split scores (one row a candidate of the UNPERMUTED grid; they
do not depend on the seed, which only orders the grid) are kept in
``R.npz`` by the first call and read by the others.  The faults that only
move scores are read off the sound search's scores, by arithmetic, for
every place they could hit: two scores swapped, the learning rate ignored
(every candidate scores what 0.1 scores at its count), the count ignored
(every candidate scores what the largest count scores at its learning
rate).
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
    ap.add_argument("--workload", default="gbc_covtype145k.lr5_stages3")
    ap.add_argument("--seed", type=int, default=2**31 + 39)
    ap.add_argument("--fault")
    ap.add_argument("--reference", required=True)
    args = ap.parse_args()

    import numpy as np

    import check
    import generate
    import rehearse_boost
    import run

    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    cell, config, traffic = run.find_cell(bench, args.workload)
    import spark_sklearn_tpu  # noqa: F401
    dev = run.require_chips(cell["chips"])
    from spark_sklearn_tpu.parallel.pipeline import enable_persistent_cache
    enable_persistent_cache()
    cv = generate.load_object(config["cv"]["class"])(**config["cv"]["params"])
    X, y = generate.make_data(config["data"])
    splits = list(cv.split(X, y))
    new_search, grid = generate.search_factory(config, traffic, args.seed)
    candidates = check.candidates_of(grid)
    spec = config["check"]

    if args.fault in rehearse_boost.PROGRAM_FAULTS:
        rehearse_boost.plant(args.fault)
    rec = run.run_search(new_search, X, y)          # compiles
    if not args.fault:                              # ... and is timed
        rec = run.run_search(new_search, X, y)
    run.describe("search", rec, len(candidates) * len(splits))
    peak = run.memory_peak_bytes()
    scores = check.split_scores(rec["cv_results"], len(splits))
    memory = rec["report"].get("memory", {})

    # every candidate, keyed by its values: the file serves every seed
    key = lambda c: json.dumps(c, sort_keys=True)
    kept = dict(np.load(args.reference)) if os.path.exists(
        args.reference) else {}
    missing = [c for c in candidates if key(c) not in kept]
    if missing:
        import time
        t0 = time.perf_counter()
        ref = run.load_named(spec["reference"])(
            X, y, splits, missing, config)[0]
        print(f"reference over {len(missing)} candidates: "
              f"{time.perf_counter() - t0:.1f} s", flush=True)
        kept.update({key(c): s for c, s in zip(missing, ref)})
        np.savez(args.reference, **kept)
    reference = np.stack([kept[key(c)] for c in candidates])
    region = check.well_defined(candidates, spec.get("well_defined"))

    out = {"fault": args.fault, "device": dev,
           "search_s": round(rec["wall_s"], 3),
           "memory_peak_bytes": peak,
           "memory_groups": memory.get("groups"),
           "peak_modeled_bytes": memory.get("peak_modeled_bytes"),
           "candidates": candidates,
           "reference_mean": reference.mean(axis=1).round(4).tolist(),
           "program": check.gap_numbers(scores, reference, region),
           "per_candidate_mean_gap": (scores - reference).mean(
               axis=1).round(5).tolist(),
           "per_candidate_max_gap": np.abs(scores - reference).max(
               axis=1).round(5).tolist()}
    # what each choice of the well-defined region would read
    for param in ("learning_rate", "n_estimators"):
        for value in sorted(set(c[param] for c in candidates)):
            where = np.array([c[param] <= value for c in candidates])
            out[f"bias_{param}_le_{value}"] = float(
                abs((scores - reference)[where].mean()))
    if not args.fault:
        n = len(candidates)
        at = {(c["learning_rate"], c["n_estimators"]): i
              for i, c in enumerate(candidates)}
        swaps = [check.gap_numbers(_swapped(scores, i, j), reference, region)
                 for i in range(n) for j in range(i + 1, n)]
        out["swapped_scores_least"] = _least(swaps)
        most = max(c["n_estimators"] for c in candidates)
        out["n_estimators_ignored"] = check.gap_numbers(
            np.stack([scores[at[c["learning_rate"], most]]
                      for c in candidates]), reference, region)
        out["learning_rate_ignored"] = check.gap_numbers(
            np.stack([scores[at[0.1, c["n_estimators"]]]
                      for c in candidates]), reference, region)
    print("fault reading: " + json.dumps(out), flush=True)
    os.makedirs(os.path.join(run.ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(run.ROOT, "chiprun_out",
                           f"boost_fault_{args.fault or 'sound'}.json"),
              "w") as f:
        json.dump(out, f)


def _swapped(scores, i, j):
    out = scores.copy()
    out[[i, j]] = out[[j, i]]
    return out


def _least(numbers):
    return {k: min(n[k] for n in numbers if k in n) for k in numbers[0]}


if __name__ == "__main__":
    main()
