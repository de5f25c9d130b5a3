"""One rehearsal of the runner on the k-NN path, in a process of its own, on
XLA:CPU.

    python3 benchmark/tests/rehearse_knn.py [--trace 1] [--fault NAME]

Drives ``run.run_cell`` on a tiny configuration of its own (``tiny_knn.json``,
``tiny_k_weights.json``) with the cell's own estimator factory, reference,
work model and per-layer readers, and prints the result line.  ``--fault``
breaks the timed path underneath the harness first; each must read
``correct: false``:

- ``own_fold_kept``: a row's own fold's rows stay among its candidate
  neighbours (the row itself at distance 0 first of all);
- ``k_off_by_one``: every candidate votes with n_neighbors + 1 neighbours;
- ``one_pass``: the distances' product in ONE bfloat16 pass, the precision
  below the configuration's (rows rounded to bfloat16 before it, as a TPU
  rounds a float32 product by default; on XLA:CPU the product is float32
  whatever precision is asked);
- ``distance_ignored``: every candidate votes uniformly;
- ``swapped_scores``: two candidates' scores change places;
- ``altered_score``: one split score moves by ``--alter`` (0.02).
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "tiny_knn.tiny_k_weights"
REAL_CELL = "knn_mnist.k30_weights2"
#: the faults that change the program, then those that only move scores
PROGRAM_FAULTS = ("own_fold_kept", "k_off_by_one", "one_pass",
                  "distance_ignored")
FAULTS = PROGRAM_FAULTS + ("swapped_scores", "altered_score")


def tiny_bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny_knn", "file": "benchmark/tests/tiny_knn.json"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny_knn",
        "traffic": "../tests/tiny_k_weights", "chips": 1})
    for m in bench["per_layer"]:
        if REAL_CELL in m.get("workloads", ()):
            m["workloads"].append(CELL)
    return bench


def plant(fault, alter=0.02):
    """Break the program under the harness; the same plants serve the
    readings at the cell's own size (``faults_at_size_knn.py``).  Returns
    a function that takes the plant out again."""
    import jax.numpy as jnp

    from spark_sklearn_tpu.models import neighbors

    family = neighbors.KNeighborsClassifierFamily
    real_tables = family._task_tables.__func__
    real_select, real_sq = neighbors.select, neighbors.sq_dists

    def retable(change):
        def tables(cls, dynamic, static, data, train_w, meta):
            return real_tables(cls, change(dynamic), static, data, train_w,
                               meta)
        family._task_tables = classmethod(tables)

    if fault == "own_fold_kept":
        neighbors.select = lambda d2, allowed, maxk: real_select(
            d2, jnp.ones_like(allowed), maxk)
    elif fault == "k_off_by_one":
        retable(lambda dyn: {**dyn, "n_neighbors": dyn["n_neighbors"] + 1})
    elif fault == "one_pass":
        def one_pass(X1, X2, sq1=None, sq2=None, precision=None):
            def bf16(a):
                return a.astype(jnp.bfloat16).astype(jnp.float32)
            return real_sq(bf16(X1), bf16(X2), sq1, sq2)
        neighbors.sq_dists = one_pass
    elif fault == "distance_ignored":
        retable(lambda dyn: {**dyn, "weights": jnp.zeros_like(
            dyn["weights"])})
    elif fault in ("swapped_scores", "altered_score"):
        import rehearse_forest
        rehearse_forest.plant(fault, alter)     # they only move scores
        return lambda: None
    else:
        raise SystemExit(f"unknown fault {fault!r}; one of {FAULTS}")

    def unplant():
        family._task_tables = classmethod(real_tables)
        neighbors.select, neighbors.sq_dists = real_select, real_sq
    return unplant


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--seed", type=int, default=2**31 + 41)
    ap.add_argument("--seconds", type=float, default=0.5)
    ap.add_argument("--fault")
    ap.add_argument("--alter", type=float, default=0.02)
    args = ap.parse_args()
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [BENCH, ROOT]
    import run
    if args.fault:
        plant(args.fault, args.alter)
    result = run.run_cell(
        tiny_bench(), CELL, args.seed, args.seconds, bool(args.trace),
        trace_dir=os.environ.get("BENCH_TEST_TRACE_DIR"))
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
