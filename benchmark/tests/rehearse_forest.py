"""One rehearsal of the runner on the forest path, in a process of its own,
on XLA:CPU.

    python3 benchmark/tests/rehearse_forest.py [--trace 1] [--fault NAME]

Drives ``run.run_cell`` on a tiny configuration of its own
(``tiny_forest.json``, ``tiny_depth_trees.json``) with the cell's own
estimator factory, reference, work model and per-layer readers, and prints
the result line.  ``--fault`` breaks the timed path underneath the harness
first; each must read ``correct: false``:

- ``no_bootstrap``: every tree grows on all the fold's training rows, each
  with weight one;
- ``one_feature_subset``: every node of a level takes the same feature
  subset;
- ``n_estimators_ignored``: every candidate grows the grid's largest
  forest;
- ``test_rows_weighted``: the fold's test rows carry weight into the
  trees;
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
CELL = "tiny_forest.tiny_depth_trees"
REAL_CELL = "forest_covtype145k.depth3_trees3"
#: the faults that change the program, then those that only move scores
PROGRAM_FAULTS = ("no_bootstrap", "one_feature_subset",
                  "n_estimators_ignored", "test_rows_weighted")
FAULTS = PROGRAM_FAULTS + ("swapped_scores", "altered_score")


def tiny_bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny_forest", "file": "benchmark/tests/tiny_forest.json"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny_forest",
        "traffic": "../tests/tiny_depth_trees", "chips": 1})
    for m in bench["per_layer"]:
        if REAL_CELL in m.get("workloads", ()):
            m["workloads"].append(CELL)
    return bench


def plant(fault, alter=0.02):
    """Break the program under the harness; the same plants serve the
    readings at the cell's own size (``faults_at_size_forest.py``)."""
    import jax
    import jax.numpy as jnp

    import spark_sklearn_tpu as sst
    from spark_sklearn_tpu.models import trees

    family = trees.RandomForestClassifierFamily
    real_fit = family.fit.__func__

    def refit(change):
        def fit(cls, dynamic, static, data, train_w, meta):
            return real_fit(cls, *change(dynamic, static, data, train_w,
                                         meta))
        family.fit = classmethod(fit)

    if fault == "no_bootstrap":
        refit(lambda dyn, st, data, w, meta:
              (dyn, {**st, "bootstrap": False}, data, w, meta))
    elif fault == "n_estimators_ignored":
        refit(lambda dyn, st, data, w, meta: (
            {**dyn, "n_estimators": jnp.asarray(
                meta["max_estimators"], jnp.int32)}, st, data, w, meta))
    elif fault == "test_rows_weighted":
        refit(lambda dyn, st, data, w, meta:
              (dyn, st, data, jnp.ones_like(w), meta))
    elif fault == "one_feature_subset":
        real_uniform = jax.random.uniform

        def same_rows(key, shape=(), *args, **kw):
            if len(shape) == 2:         # the grower's (nodes, d) scores
                return jnp.broadcast_to(
                    real_uniform(key, (1, shape[1]), *args, **kw), shape)
            return real_uniform(key, shape, *args, **kw)
        from spark_sklearn_tpu.ops import trees as ops_trees
        ops_trees.jax = _Patched(
            jax, random=_Patched(jax.random, uniform=same_rows))
    elif fault in ("swapped_scores", "altered_score"):
        real_search_fit = sst.GridSearchCV.fit

        def wrong(self, X, y=None, **kw):
            out = real_search_fit(self, X, y, **kw)
            for key, col in self.cv_results_.items():
                if key.startswith("split") and key.endswith("_test_score"):
                    if fault == "swapped_scores":
                        col[[0, 1]] = col[[1, 0]]
                    elif key.startswith("split0"):
                        col[0] += alter
            if fault == "altered_score":
                # an answer that is wrong and consistent: the mean and
                # the ranks follow the moved score
                import numpy as np
                from scipy.stats import rankdata
                res = self.cv_results_
                n_folds = sum(k.startswith("split") and k.endswith(
                    "_test_score") for k in res)
                res["mean_test_score"] = np.mean(
                    [res[f"split{i}_test_score"] for i in range(n_folds)],
                    axis=0)
                res["rank_test_score"] = rankdata(
                    -res["mean_test_score"], method="min").astype(np.int32)
            return out
        sst.GridSearchCV.fit = wrong
    else:
        raise SystemExit(f"unknown fault {fault!r}; one of {FAULTS}")


class _Patched:
    """A module with some attributes replaced, everything else its own."""

    def __init__(self, module, **replaced):
        self._module = module
        self.__dict__.update(replaced)

    def __getattr__(self, name):
        return getattr(self._module, name)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--seed", type=int, default=2**31 + 35)
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
