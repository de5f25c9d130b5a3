"""One rehearsal of the runner on the minibatch-perceptron path, in a
process of its own, on XLA:CPU.

    python3 benchmark/tests/rehearse_mlp.py [--trace 1] [--fault NAME]

Drives ``run.run_cell`` on a tiny configuration of its own
(``tiny_mlp.json``, ``tiny_arch_alpha.json``) with the cell's own
estimator factory, reference, work model and per-layer readers, and prints
the result line.  ``--fault`` breaks the timed path underneath the harness
first; each must read ``correct: false``:

- ``initial_weights``: the minibatch loop runs no step, so the weights
  stay at their initial values;
- ``scaler_all_rows``: the scaler's statistics come from every row, the
  fold's test rows among them;
- ``swapped_scores``: two candidates' scores change places;
- ``one_epoch``: one epoch instead of the configured count;
- ``no_second_moment``: Adam's second moment stays at zero.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "tiny_mlp.tiny_arch_alpha"
FAULTS = ("initial_weights", "scaler_all_rows", "swapped_scores",
          "one_epoch", "no_second_moment")


def tiny_bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny_mlp", "file": "benchmark/tests/tiny_mlp.json"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny_mlp",
        "traffic": "../tests/tiny_arch_alpha", "chips": 1})
    for m in bench["per_layer"]:
        if "mlp_mnist.arch_alpha" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    return bench


def plant(fault):
    import jax
    import jax.numpy as jnp

    from spark_sklearn_tpu.models import mlp, pipeline

    if fault == "initial_weights":
        real = jax.lax.fori_loop

        def no_steps(lo, hi, body, init):
            return real(lo, lo, body, init)
        mlp.jax = _Patched(jax, lax=_Patched(jax.lax, fori_loop=no_steps))
    elif fault == "scaler_all_rows":
        real_fit = pipeline.PipelineFamily.prefix_transform

        def all_rows(self, static, data, fold_w):
            return real_fit(self, static, data, jnp.ones_like(fold_w))
        pipeline.PipelineFamily.prefix_transform = all_rows
    elif fault == "swapped_scores":
        import spark_sklearn_tpu as sst
        real_fit = sst.GridSearchCV.fit

        def swapped(self, X, y=None, **kw):
            out = real_fit(self, X, y, **kw)
            for key, col in self.cv_results_.items():
                if key.startswith("split") and key.endswith("_test_score"):
                    col[[0, 1]] = col[[1, 0]]
            return out
        sst.GridSearchCV.fit = swapped
    elif fault == "one_epoch":
        real = mlp.MLPClassifierFamily.fit.__func__

        def one_epoch(cls, dynamic, static, data, train_w, meta):
            return real(cls, dynamic, {**static, "max_iter": 1}, data,
                        train_w, meta)
        mlp.MLPClassifierFamily.fit = classmethod(one_epoch)
    elif fault == "no_second_moment":
        real = mlp.MLPClassifierFamily.fit.__func__

        def no_v(cls, dynamic, static, data, train_w, meta):
            return real(cls, dynamic, {**static, "beta_2": 1.0}, data,
                        train_w, meta)
        mlp.MLPClassifierFamily.fit = classmethod(no_v)
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
    ap.add_argument("--seed", type=int, default=2**31 + 33)
    ap.add_argument("--seconds", type=float, default=0.5)
    ap.add_argument("--fault")
    args = ap.parse_args()
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [BENCH, ROOT]
    import run
    if args.fault:
        plant(args.fault)
    result = run.run_cell(
        tiny_bench(), CELL, args.seed, args.seconds, bool(args.trace),
        trace_dir=os.environ.get("BENCH_TEST_TRACE_DIR"))
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
