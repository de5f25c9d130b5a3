"""Multi-process (multi-controller) dryrun — SURVEY §3.5 / §7.3 #6.

The reference's multi-node story is Spark's: driver + executor JVMs over
Netty (reference: util.py createLocalSparkSession is the local[*] stand-in).
The TPU-native story is JAX multi-controller SPMD: every host runs the
same program, `jax.distributed.initialize` wires the control plane, and
the mesh spans all hosts' devices so XLA collectives ride ICI/DCN.

Everything else in the engine is "same code, bigger mesh" — the one thing
a single-process virtual mesh cannot exercise is the multi-host bootstrap
and the cross-process gather of launch outputs
(`parallel.mesh.device_get_tree`).  `dryrun_multihost(n_proc, n_dev)`
exercises exactly that on CPU devices: it spawns n_proc REAL OS processes,
each claiming n_dev virtual CPU devices, forms a (n_proc*n_dev)-device
cluster, and runs one small GridSearchCV sweep through the public API
with the task grid sharded across processes.

CPU-pinned by design: every worker forces ``jax_platforms="cpu"``, so
this never touches an accelerator (a chip belongs to one process at a
time — n_proc workers could not share it) and nothing it prints is a
chip result.  The chip is reached only by ``chip_smoke.py``.

Run directly:  python -m spark_sklearn_tpu.utils.multihost
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import time


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def worker_main(coordinator: str, n_proc: int, pid: int, n_dev: int) -> int:
    """One cluster process: claim n_dev virtual CPU devices, join the
    jax.distributed cluster, run a sharded search over the GLOBAL mesh."""
    import jax

    # CPU-pinned by design (see the module docstring); the platform and
    # the device count must be fixed before any backend init
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_dev)

    from spark_sklearn_tpu.utils.session import init_distributed
    init_distributed(coordinator_address=coordinator,
                     num_processes=n_proc, process_id=pid)

    assert jax.process_count() == n_proc, jax.process_count()
    assert jax.device_count() == n_proc * n_dev, jax.device_count()
    assert jax.local_device_count() == n_dev

    import numpy as np
    from sklearn.linear_model import LogisticRegression

    import spark_sklearn_tpu as sst

    rng = np.random.default_rng(0)
    X = rng.normal(size=(64, 6)).astype(np.float32)
    y = (X[:, 0] + 0.2 * rng.normal(size=64) > 0).astype(np.int64)

    # global mesh over every process's devices: the task axis spans the
    # cluster, so each process computes its stripe of the candidate grid
    # and `device_get_tree` all-gathers the scores
    config = sst.TpuConfig(devices=jax.devices())
    gs = sst.GridSearchCV(
        LogisticRegression(max_iter=20),
        {"C": [0.05, 0.1, 0.5, 1.0, 2.0, 4.0, 8.0, 16.0]},
        cv=2, refit=False, backend="tpu", config=config)
    gs.fit(X, y)
    scores = gs.cv_results_["mean_test_score"]
    assert np.all(np.isfinite(scores)), scores
    assert float(scores.max()) > 0.5, scores
    # public report surface; degrade to an empty mesh dict if fit has
    # not populated it (NotFittedError is also an AttributeError)
    try:
        mesh_shape = dict(gs.search_report.get("mesh", {}))
    except AttributeError:
        mesh_shape = {}
    print(f"proc {pid}/{n_proc}: {jax.local_device_count()} local of "
          f"{jax.device_count()} global devices, mesh={mesh_shape}, "
          f"best={float(scores.max()):.3f}", flush=True)
    return 0


def _wait_procs(procs, timeout_s: float, grace_s: float = 10.0):
    """Reap a cluster's worker processes under one shared deadline.

    Per-worker semantics: each process must exit before `timeout_s`
    elapses (a shared wall — a multi-controller cluster's workers
    finish together or not at all).  The moment ANY worker fails or
    times out, the rest get `grace_s` to exit (their peer's death
    typically wedges their next collective forever) and are then
    killed and reaped — no straggler is ever left waiting without a
    deadline.

    Returns (outs, failed_idx, timed_out_idx): per-process output
    strings and the process indices that exited nonzero / were killed.
    """
    import threading

    # drain every worker's stdout on a reader thread: a chatty worker
    # (crash tracebacks, verbose XLA logs) would otherwise fill the OS
    # pipe buffer, block in write(), and look "hung" until the deadline
    drained: dict = {}

    def _reader(pid, stream):
        try:
            drained[pid] = stream.read() or ""
        except (OSError, ValueError):         # pragma: no cover
            drained[pid] = "<output unreadable>"

    readers = {}
    for pid, p in enumerate(procs):
        if p.stdout is not None:
            t = threading.Thread(target=_reader, args=(pid, p.stdout),
                                 daemon=True)
            t.start()
            readers[pid] = t

    deadline = time.time() + timeout_s
    pending = dict(enumerate(procs))
    failed_idx, timed_out_idx = [], []
    while pending and time.time() < deadline:
        for pid in list(pending):
            p = pending[pid]
            if p.poll() is None:
                continue
            del pending[pid]
            if p.returncode != 0:
                failed_idx.append(pid)
                # fail fast: a dead cluster process wedges its peers'
                # next collective — give them a short grace, not the
                # whole budget
                deadline = min(deadline, time.time() + grace_s)
        if pending:
            time.sleep(0.1)
    for pid, p in sorted(pending.items()):   # stragglers: kill and reap
        timed_out_idx.append(pid)
        p.kill()
        try:
            p.wait(timeout=30)
        except subprocess.TimeoutExpired:     # pragma: no cover
            pass
    outs = []
    for pid, p in enumerate(procs):
        t = readers.get(pid)
        if t is not None:
            t.join(timeout=30)
        out = drained.get(pid, "")
        if pid in timed_out_idx:
            out += "\n<killed: exceeded deadline>"
        outs.append(out)
    return outs, sorted(failed_idx), sorted(timed_out_idx)


def dryrun_multihost(n_proc: int = 2, n_dev: int = 2,
                     timeout_s: int = 600) -> None:
    """Spawn an n_proc-process CPU cluster and run one sharded search.

    Raises RuntimeError naming WHICH process index died (plus every
    process's output) on failure, so a sandbox that forbids
    subprocesses or localhost sockets is flagged clearly rather than
    silently skipped.  Worker waits carry a per-worker deadline: a hung
    worker is killed and reaped, never awaited forever."""
    coordinator = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)   # worker pins platform itself
    procs = []
    for pid in range(n_proc):
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "spark_sklearn_tpu.utils.multihost",
             "--worker", coordinator, str(n_proc), str(pid), str(n_dev)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env))
    outs, failed_idx, timed_out_idx = _wait_procs(procs, timeout_s)
    if failed_idx or timed_out_idx:
        blame = []
        if failed_idx:
            blame.append("proc(s) %s exited nonzero (%s)" % (
                failed_idx,
                ", ".join(f"{i}: rc={procs[i].returncode}"
                          for i in failed_idx)))
        if timed_out_idx:
            blame.append(f"proc(s) {timed_out_idx} killed after "
                         f"{timeout_s}s deadline")
        detail = "\n".join(
            f"--- proc {pid} (rc={p.returncode}) ---\n{outs[pid]}"
            for pid, p in enumerate(procs))
        raise RuntimeError(
            "dryrun_multihost failed: " + "; ".join(blame)
            + " (sandbox may forbid subprocesses or localhost "
            "sockets):\n" + detail)
    for pid, o in enumerate(outs):
        print(f"--- proc {pid} (rc=0) ---\n{o}".strip())
    print(f"dryrun_multihost({n_proc} procs x {n_dev} devices) OK")


def main(argv):
    if len(argv) >= 6 and argv[1] == "--worker":
        return worker_main(argv[2], int(argv[3]), int(argv[4]),
                           int(argv[5]))
    dryrun_multihost()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
