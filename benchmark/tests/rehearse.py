"""One rehearsal of the runner in a process of its own, on XLA:CPU.

    python3 benchmark/tests/rehearse.py --chips 1|4 [--trace 1] [--fault F]

Drives ``run.run_cell`` — everything of a run but the demand for a chip —
on a tiny configuration of its own (``tiny_logreg.json``, ``tiny_grid.json``)
and prints the result line.  ``--chips 4`` gives the CPU backend four
virtual devices, so the search shards its lanes over a four-device mesh as
a ``chips: 4`` cell does.  ``--fault`` breaks the timed path underneath the
harness first:

- ``unchanged``: the solver returns its state as it got it;
- ``half``: half of the training rows are left out of every fit;
- ``swapped``: two candidates' scores land at each other's place;
- ``altered``: one split score is altered where it is produced.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def tiny_bench(chips):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny_logreg", "file": "benchmark/tests/tiny_logreg.json"})
    bench["workloads"].append({
        "name": "tiny_logreg.tiny_grid", "config": "tiny_logreg",
        "traffic": "../tests/tiny_grid", "chips": chips})
    for m in bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny_logreg.tiny_grid")
    return bench


def plant(fault):
    import numpy as np

    import spark_sklearn_tpu as sst
    from spark_sklearn_tpu.models.linear import LogisticRegressionFamily
    from spark_sklearn_tpu.ops import solvers

    if fault == "unchanged":
        def stuck(Ax, data_loss, data_grad, AT, reg_loss, reg_grad, x0,
                  **kw):
            import jax.numpy as jnp
            B = x0.shape[0]
            zeros = jnp.zeros((B,), x0.dtype)
            return solvers.LBFGSResult(
                x=x0, fun=zeros, grad_norm=zeros,
                n_iter=jnp.zeros((B,), jnp.int32),
                converged=jnp.zeros((B,), bool))
        solvers.glm_lbfgs_batched = stuck
    elif fault == "half":
        inner = LogisticRegressionFamily.fit_task_batched.__func__

        def half(cls, dynamic, static, data, train_w, meta):
            import jax.numpy as jnp
            keep = (jnp.arange(train_w.shape[-1]) % 2).astype(train_w.dtype)
            return inner(cls, dynamic, static, data, train_w * keep, meta)
        LogisticRegressionFamily.fit_task_batched = classmethod(half)
    elif fault in ("swapped", "altered"):
        fit = sst.GridSearchCV.fit

        def broken(self, X, y=None, **kw):
            out = fit(self, X, y, **kw)
            res = self.cv_results_
            keys = [k for k in res if k.endswith("_test_score")]
            if fault == "swapped":
                for k in keys:      # first and last candidate trade places
                    v = np.array(res[k])
                    v[[0, -1]] = v[[-1, 0]]
                    res[k] = v
            else:
                v = np.array(res["split0_test_score"])
                v[0] = min(v[0] + 0.05, 1.0)
                res["split0_test_score"] = v
                res["mean_test_score"] = np.mean(
                    [res[f"split{i}_test_score"] for i in range(5)], axis=0)
            return out
        sst.GridSearchCV.fit = broken
    else:
        raise SystemExit(f"unknown fault {fault!r}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--seed", type=int, default=2**31 + 11)
    ap.add_argument("--seconds", type=float, default=0.5)
    ap.add_argument("--fault")
    args = ap.parse_args()
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={args.chips}")
    sys.path[:0] = [BENCH, ROOT]
    import run
    if args.fault:
        plant(args.fault)
    result = run.run_cell(
        tiny_bench(args.chips), "tiny_logreg.tiny_grid", args.seed,
        args.seconds, bool(args.trace),
        trace_dir=os.environ.get("BENCH_TEST_TRACE_DIR"))
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
