"""One rehearsal of the runner on the boosting path, in a process of its
own, on XLA:CPU.

    python3 benchmark/tests/rehearse_boost.py [--trace 1] [--fault NAME]

Drives ``run.run_cell`` on a tiny configuration of its own
(``tiny_boost.json``, ``tiny_lr_stages.json``) with the cell's own estimator
factory, reference, work model and per-layer readers, and prints the result
line.  ``--fault`` breaks the timed path underneath the harness first; each
must read ``correct: false``:

- ``test_rows_weighted``: the fold's test rows carry weight into the trees;
- ``learning_rate_ignored``: every lane steps at 0.1;
- ``n_estimators_ignored``: every lane runs the grid's largest count;
- ``unit_hessians``: every row's hessian is 1 (first-order leaves);
- ``two_trees_a_stage``: a tree a class on the softmax's gradients, as the
  program grew them before the binary path;
- ``one_part``: a row's statistics enter the histograms as ONE bfloat16
  part (the control: the precision below the configuration's; on XLA:CPU the
  grower runs its plain float32 form, so there the plant rounds the
  statistics to bfloat16 before it);
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
CELL = "tiny_boost.tiny_lr_stages"
REAL_CELL = "gbc_covtype145k.lr5_stages3"
#: the faults that change the program, then those that only move scores
PROGRAM_FAULTS = ("test_rows_weighted", "learning_rate_ignored",
                  "n_estimators_ignored", "unit_hessians",
                  "two_trees_a_stage", "one_part")
FAULTS = PROGRAM_FAULTS + ("swapped_scores", "altered_score")


def tiny_bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny_boost", "file": "benchmark/tests/tiny_boost.json"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny_boost",
        "traffic": "../tests/tiny_lr_stages", "chips": 1})
    for m in bench["per_layer"]:
        if REAL_CELL in m.get("workloads", ()):
            m["workloads"].append(CELL)
    return bench


def plant(fault, alter=0.02):
    """Break the program under the harness; the same plants serve the
    readings at the cell's own size (``faults_at_size_boost.py``)."""
    import jax.numpy as jnp

    from spark_sklearn_tpu.models import trees
    from spark_sklearn_tpu.ops import tree_hist

    family = trees.GradientBoostingClassifierFamily
    real_boost = family._boost.__func__

    def reboost(change):
        def boost(cls, dynamic, static, data, train_w, meta):
            return real_boost(cls, *change(dynamic, static, data, train_w,
                                           meta))
        family._boost = classmethod(boost)

    if fault == "test_rows_weighted":
        reboost(lambda dyn, st, data, w, meta:
                (dyn, st, data, jnp.ones_like(w), meta))
    elif fault == "learning_rate_ignored":
        reboost(lambda dyn, st, data, w, meta: (
            {**dyn, "learning_rate": jnp.float32(0.1)}, st, data, w, meta))
    elif fault == "n_estimators_ignored":
        reboost(lambda dyn, st, data, w, meta: (
            {**dyn, "n_estimators": jnp.asarray(
                meta["max_estimators"], jnp.int32)}, st, data, w, meta))
    elif fault == "unit_hessians":
        real = family._grad_hess.__func__

        def grad_hess(cls, mean, data):
            g, h = real(cls, mean, data)
            return g, jnp.ones_like(h)
        family._grad_hess = classmethod(grad_hess)
    elif fault == "two_trees_a_stage":
        # the parent's model: F (n, 2) from the log-priors, a tree a class
        family._trees_per_stage = classmethod(
            lambda cls, meta: int(meta["n_classes"]))
    elif fault == "one_part":
        real_grouped = tree_hist.GroupedLevels.__init__
        real_plain = tree_hist.PlainLevels.__init__

        def grouped(self, codes, stats, n_bins, integer_stats=False, **kw):
            # what `integer_stats` selects for a forest's integers: one
            # part packed, one part multiplied
            real_grouped(self, codes, stats, n_bins, True, **kw)

        def plain(self, codes, stats, n_bins, integer_stats=False):
            real_plain(self, codes, stats.astype(jnp.bfloat16).astype(
                jnp.float32), n_bins, integer_stats)
        tree_hist.GroupedLevels.__init__ = grouped
        tree_hist.PlainLevels.__init__ = plain
    elif fault in ("swapped_scores", "altered_score"):
        import rehearse_forest
        rehearse_forest.plant(fault, alter)     # they only move scores
    else:
        raise SystemExit(f"unknown fault {fault!r}; one of {FAULTS}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--seed", type=int, default=2**31 + 39)
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
