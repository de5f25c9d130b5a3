"""One rehearsal of the runner on the kernel-SVM path, in a process of its
own, on XLA:CPU.

    python3 benchmark/tests/rehearse_svc.py [--trace 1] [--fault zero_alphas]

Drives ``run.run_cell`` on a tiny configuration of its own
(``tiny_svc.json``, ``tiny_c2_gamma2.json``) with the cell's own reference,
work model and per-layer readers, and prints the result line.
``--fault zero_alphas`` breaks the timed path underneath the harness first:
the dual's solver returns its alphas as it got them, at zero.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "tiny_svc.tiny_c2_gamma2"


def tiny_bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny_svc", "file": "benchmark/tests/tiny_svc.json"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny_svc",
        "traffic": "../tests/tiny_c2_gamma2", "chips": 1})
    for m in bench["per_layer"]:
        if "svc_rbf_mnist20k.c4_gamma4" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    return bench


def plant(fault):
    from spark_sklearn_tpu.models import svm

    if fault != "zero_alphas":
        raise SystemExit(f"unknown fault {fault!r}")

    def stuck(grad, project, x0, step, max_iter, tol, dtype):
        import jax.numpy as jnp
        return x0, jnp.asarray(0, jnp.int32)
    svm._run_dual = stuck


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--seed", type=int, default=2**31 + 11)
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
