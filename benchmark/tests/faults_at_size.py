#!/usr/bin/env python3
"""The planted faults' readings at the MLP cell's OWN size, on the chip the
cell asks for: what ``correct`` compares, for one whole search with one of
``rehearse_mlp.py``'s faults planted underneath it (or with none).

    python3 benchmark/tests/faults_at_size.py --reference R.npy \\
        [--fault NAME] [--workload mlp_mnist.arch_alpha] [--seed N]

One process a fault: the program store would hand a second search of the
same process the sound programs.  The reference's split scores are kept in
``R.npy`` by the first call and read by the others.  ``swapped_scores`` needs
no fit: it is read off the sound search's scores.  The limits' upper
readings where the bfloat16 control gives none come from these (PERF.md
section 2).
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
    ap.add_argument("--workload", default="mlp_mnist.arch_alpha")
    ap.add_argument("--seed", type=int, default=2**31 + 33)
    ap.add_argument("--fault")
    ap.add_argument("--reference", required=True)
    args = ap.parse_args()

    import numpy as np

    import check
    import generate
    import rehearse_mlp
    import run

    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    cell, config, traffic = run.find_cell(bench, args.workload)
    import spark_sklearn_tpu  # noqa: F401
    run.require_chips(cell["chips"])
    from spark_sklearn_tpu.parallel.pipeline import enable_persistent_cache
    enable_persistent_cache()
    cv = generate.load_object(config["cv"]["class"])(**config["cv"]["params"])
    X, y = generate.make_data(config["data"])
    splits = list(cv.split(X, y))
    new_search, grid = generate.search_factory(config, traffic, args.seed)
    candidates = check.candidates_of(grid)
    spec = config["check"]
    assert spec["n_candidates"] == len(candidates), "every candidate"
    sample = np.arange(len(candidates))
    if os.path.exists(args.reference):
        reference = np.load(args.reference)
    else:
        sample, reference = check.reference_sample(
            grid, X, y, splits, config, args.seed,
            run.load_named(spec["reference"]))[1:]
        np.save(args.reference, reference)
    if args.fault and args.fault != "swapped_scores":
        rehearse_mlp.plant(args.fault)
    rec = run.run_search(new_search, X, y)
    if args.fault == "swapped_scores":
        for key, col in rec["cv_results"].items():
            if key.startswith("split") and key.endswith("_test_score"):
                col[[0, 1]] = col[[1, 0]]
    compared, correct = check.compare(
        [rec["cv_results"]], candidates, sample, reference, spec)
    print("fault reading: " + json.dumps({
        "fault": args.fault, "correct": correct,
        "search_s": round(rec["wall_s"], 3),
        "numbers": {k: v["value"] for k, v in compared.items()}}),
        flush=True)


if __name__ == "__main__":
    main()
