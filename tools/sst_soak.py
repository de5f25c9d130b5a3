"""Chaos soak harness for the self-protecting search service.

Drives M tenants x N searches through one shared
:class:`~spark_sklearn_tpu.utils.session.TpuSession` under a
deterministic chaos plan, then asserts the service's protection
contract held:

  - **zero process crashes** — the harness itself finishes, the
    executor still admits and completes a clean search afterwards;
  - **every search accounted for** — each submission ends exactly one
    of: bit-exact vs its solo baseline, cleanly rejected with a
    machine-readable :class:`AdmissionError`, or explicitly partial
    with a ``search_report["protection"]`` block naming EVERY shed or
    quarantined candidate;
  - **bounded p95 queue wait** — no tenant's telemetry queue-wait p95
    exceeds ``--max-p95``.

The chaos plan is a superset of the ``TpuConfig(fault_plan)`` grammar
(parallel/faults.py): launch-fault tokens are distributed round-robin
onto the tenants' fault plans, and two session-level event tokens run
on the harness clock:

  ============================  =====================================
  token                         event
  ============================  =====================================
  ``transient@N[xK]``           retryable launch failure(s)
  ``oom@N`` / ``oom_deep@N``    chunk OOM / sticky deep OOM
  ``hung@N``                    wedged launch (watchdog recovers)
  ``fatal@N`` / ``fatal_deep@N``  poison launch / sticky poison range
  ``slow@N:F``                  brownout: launch N stalls F seconds
  ``submit_storm@T[xK]``        at T s, K threads race session.submit
  ``evict_storm@T``             at T s, distinct-content submissions
                                churn the device data plane
  ============================  =====================================

    python tools/sst_soak.py                       # default soak
    python tools/sst_soak.py --tenants 3 --searches 4 \
        --plan "transient@1;oom_deep@2;hung@1;slow@3:0.3;submit_storm@0x6"

``--crash-drill`` runs the crash-safety arc instead: a child process
journals a search (``serve/journal.py``) and is ``kill -9``ed
mid-flight once its checkpoint journal holds at least one chunk; the
harness then fences the dead owner's lease, recovers the journaled
search through ``TpuSession.recover()`` / ``resubmit()``, and asserts
the recovered ``cv_results_`` is bit-exact (``np.array_equal``)
against the uncrashed baseline, the crash-marker flight bundle
landed, and the journal owes nothing afterwards.

The crash drill's victim child is CPU-pinned by design (the harness
process and its child cannot both hold one chip — a chip belongs to one
process at a time): the drill checks journal recovery, never the
accelerator, and nothing it prints is a chip result.

Exits nonzero when any assertion fails; ``--json`` emits the full
per-search ledger for CI artifacts.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

# runnable as `python tools/sst_soak.py` from a checkout: the repo
# root (the package's parent) joins sys.path like `python -m` would
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

__all__ = ["parse_chaos_plan", "run_crash_drill", "run_soak", "main"]

#: session-level events: (name, t_s, count)
_EVENT_RE = re.compile(
    r"(?i)^(submit_storm|evict_storm)@([0-9.]+)(?:x(\d+))?$")

#: the default plan: transients, one deep OOM, a hang, a brownout, a
#: sticky poison range and a submit storm — every protection layer
#: fires at least once
DEFAULT_PLAN = ("transient@1;oom_deep@2;fatal_deep@3;slow@3:0.3;"
                "hung@5;submit_storm@0x6")


def parse_chaos_plan(plan: str) -> Tuple[List[str], List[Tuple[str,
                                                               float,
                                                               int]]]:
    """Split a chaos plan into (launch-fault tokens, session events).
    Launch tokens are validated against the fault-plan grammar so a
    typo fails at harness start, not mid-soak."""
    from spark_sklearn_tpu.parallel.faults import FaultPlan
    tokens: List[str] = []
    events: List[Tuple[str, float, int]] = []
    for raw in re.split(r"[;,]", plan or ""):
        tok = raw.strip()
        if not tok:
            continue
        m = _EVENT_RE.match(tok)
        if m:
            events.append((m.group(1).lower(), float(m.group(2)),
                           int(m.group(3) or 1)))
            continue
        FaultPlan.parse(tok)        # raises on a malformed token
        tokens.append(tok)
    return tokens, sorted(events, key=lambda e: e[1])


def _make_search(sst, cfg, seed: int):
    from sklearn.linear_model import LogisticRegression
    import numpy as np
    c_grid = np.logspace(-2 + 0.01 * seed, 1, 12).tolist()
    return sst.GridSearchCV(
        LogisticRegression(max_iter=10), {"C": c_grid}, cv=2,
        refit=False, backend="tpu", error_score=-999.0, config=cfg)


def _drill_data():
    """The crash drill's dataset — one definition imported by BOTH the
    to-be-killed child and the recovering harness, so the fingerprint
    check in ``TpuSession.resubmit()`` compares like with like."""
    import numpy as np
    rng = np.random.RandomState(11)
    X = rng.randn(120, 6).astype(np.float32)
    y = (X[:, 0] + 0.25 * rng.randn(120) > 0).astype(np.int64)
    return X, y


def _classify(search, fut, baseline, n_cand: int) -> Dict[str, Any]:
    """One submission's verdict: exact / partial-declared / failed."""
    import numpy as np
    try:
        fut.result()
    except Exception as exc:   # noqa: BLE001 — the soak LEDGERS
        # failures instead of crashing; anything landing here fails
        # the zero-crash assertion below with its type on record
        return {"outcome": "failed",
                "error": f"{type(exc).__name__}: {exc}"[:300]}
    prot = (search.search_report or {}).get("protection") or {}
    scores = search.cv_results_["mean_test_score"]
    declared = sorted({int(i)
                       for entry in (list(prot.get("shed") or [])
                                     + list(prot.get("quarantined")
                                            or []))
                       for i in entry.get("candidates", [])})
    if prot.get("partial"):
        # every non-declared candidate must still be bit-exact, and
        # every declared one must carry error_score
        undeclared = [i for i in range(n_cand) if i not in declared]
        ok = (all(scores[i] == -999.0 for i in declared)
              and bool(np.allclose(scores[undeclared],
                                   baseline[undeclared]))
              if declared else False)
        return {"outcome": "partial-declared" if ok else "failed",
                "verdict": prot.get("verdict", ""),
                "n_declared": len(declared),
                "error": None if ok else
                "partial block does not name every missing candidate"}
    if np.allclose(scores, baseline):
        return {"outcome": "exact", "verdict": prot.get("verdict",
                                                        "complete")}
    return {"outcome": "failed",
            "error": "scores diverged without a declared-partial "
                     "protection block"}


def run_soak(n_tenants: int = 2, n_searches: int = 3,
             plan: str = DEFAULT_PLAN, deadline_s: float = 120.0,
             max_p95_s: float = 60.0, quarantine_k: int = 2,
             launch_timeout_s: float = 20.0,
             verbose: bool = True) -> Dict[str, Any]:
    import numpy as np
    import spark_sklearn_tpu as sst
    from spark_sklearn_tpu.obs import telemetry as _telemetry
    from spark_sklearn_tpu.serve.executor import AdmissionError

    def say(msg: str) -> None:
        if verbose:
            print(f"[soak] {msg}", flush=True)

    tokens, events = parse_chaos_plan(plan)
    rng = np.random.RandomState(7)
    X = rng.randn(96, 6).astype(np.float32)
    y = (X[:, 0] + 0.25 * rng.randn(96) > 0).astype(np.int64)

    # one clean solo baseline per seed (protection off, no faults)
    say(f"baselines for {n_searches} search shape(s)")
    baselines: Dict[int, Any] = {}
    for seed in range(n_searches):
        solo = _make_search(sst, None, seed)
        solo.fit(X, y)
        baselines[seed] = solo.cv_results_["mean_test_score"].copy()

    # tenant configs: protection on everywhere, launch faults
    # round-robin over tenants
    tenant_plans: List[List[str]] = [[] for _ in range(n_tenants)]
    for i, tok in enumerate(tokens):
        tenant_plans[i % n_tenants].append(tok)
    from spark_sklearn_tpu.parallel.faults import FaultPlan
    for t, tp in enumerate(tenant_plans):
        if tp:
            # fail at harness start (duplicate indices after the
            # round-robin split), not inside a soak thread
            FaultPlan.parse(",".join(tp))

    def tenant_cfg(t: int, fault_tokens: List[str]):
        return sst.TpuConfig(
            tenant=f"tenant{t}", partial_results="best_effort",
            search_deadline_s=deadline_s, admission_mode="predictive",
            quarantine_fatal_k=quarantine_k,
            launch_timeout_s=launch_timeout_s,
            max_tasks_per_batch=8, telemetry_port=0,
            max_concurrent_searches=2, max_queued_searches=4,
            fault_plan=",".join(fault_tokens) or None)

    session_cfg = tenant_cfg(0, [])
    sess = sst.createLocalTpuSession("sst-soak", session_cfg)
    ledger: List[Dict[str, Any]] = []
    ledger_lock = threading.Lock()
    t0 = time.perf_counter()

    def submit_one(t: int, seed: int, fault_tokens: List[str],
                   tag: str, data=None) -> None:
        cfg = tenant_cfg(t, fault_tokens)
        search = _make_search(sst, cfg, seed)
        rec: Dict[str, Any] = {"tenant": f"tenant{t}", "seed": seed,
                               "tag": tag,
                               "faults": ",".join(fault_tokens)}
        Xs, ys = data if data is not None else (X, y)
        try:
            t_sub = time.perf_counter()
            fut = sess.submit(search, Xs, ys)
        except AdmissionError as exc:
            rec.update(outcome="rejected-clean",
                       reason=exc.reason,
                       retry_after_s=exc.retry_after_s)
            with ledger_lock:
                ledger.append(rec)
            return
        rec.update(_classify(search, fut, baselines[seed],
                             len(baselines[seed])))
        rec["wall_s"] = round(time.perf_counter() - t_sub, 3)
        with ledger_lock:
            ledger.append(rec)

    # main soak wave: every tenant submits its searches on its own
    # thread while the event clock fires storms
    say(f"soak wave: {n_tenants} tenant(s) x {n_searches} search(es), "
        f"faults={tokens}, events={events}")
    threads: List[threading.Thread] = []
    for t in range(n_tenants):
        def tenant_body(t=t):
            for seed in range(n_searches):
                # the tenant's fault plan applies to its FIRST search
                # (fault indices are per-search); later ones run clean
                submit_one(t, seed,
                           tenant_plans[t] if seed == 0 else [],
                           tag="wave")
        th = threading.Thread(target=tenant_body,
                              name=f"soak-tenant{t}")
        th.start()
        threads.append(th)

    for name, t_s, count in events:
        delay = t_s - (time.perf_counter() - t0)
        if delay > 0:
            time.sleep(delay)
        if name == "submit_storm":
            say(f"submit storm: {count} racing submission(s)")
            storm: List[threading.Thread] = []
            for k in range(count):
                th = threading.Thread(
                    target=submit_one,
                    args=(k % n_tenants, k % n_searches, [],
                          "storm"),
                    name=f"soak-storm{k}")
                th.start()
                storm.append(th)
            threads.extend(storm)
        elif name == "evict_storm":
            say(f"evict storm: {count} distinct-content "
                "submission(s)")
            for k in range(count):
                Xk = X + np.float32(1e-6 * (k + 1))
                th = threading.Thread(
                    target=submit_one,
                    args=(k % n_tenants, k % n_searches, [],
                          "evict"),
                    kwargs={"data": (Xk, y)},
                    name=f"soak-evict{k}")
                th.start()
                threads.append(th)

    for th in threads:
        th.join()

    # liveness proof: the executor must still admit and complete a
    # clean search AFTER the chaos
    say("post-chaos liveness probe")
    submit_one(0, 0, [], tag="liveness")

    snap = _telemetry.get_telemetry().snapshot()
    sess.stop()

    by_outcome: Dict[str, int] = {}
    for rec in ledger:
        by_outcome[rec["outcome"]] = by_outcome.get(rec["outcome"],
                                                    0) + 1
    p95 = {name: float(t.get("queue_wait_p95_s", 0.0) or 0.0)
           for name, t in (snap.get("tenants") or {}).items()}
    failures: List[str] = []
    for rec in ledger:
        if rec["outcome"] == "failed":
            failures.append(
                f"{rec['tenant']} seed={rec['seed']} tag={rec['tag']}: "
                f"{rec.get('error')}")
    live = [r for r in ledger if r["tag"] == "liveness"]
    if not live or live[-1]["outcome"] != "exact":
        failures.append("post-chaos liveness probe did not complete "
                        "bit-exact")
    worst_p95 = max(p95.values(), default=0.0)
    if worst_p95 > max_p95_s:
        failures.append(f"queue-wait p95 {worst_p95:.2f}s exceeds "
                        f"bound {max_p95_s:.2f}s")

    result = {
        "n_submissions": len(ledger),
        "by_outcome": by_outcome,
        "queue_wait_p95_s": p95,
        "protection_counters": snap.get("protection") or {},
        "failures": failures,
        "ledger": ledger,
    }
    say(f"outcomes: {by_outcome}; protection counters: "
        f"{result['protection_counters']}")
    if failures:
        for f in failures:
            say(f"FAILURE: {f}")
    else:
        say("SOAK GREEN: zero crashes, every search exact / "
            "cleanly-rejected / declared-partial")
    return result


#: the child half of the crash drill: journal + checkpoint a search
#: stretched by a brownout plan, then hang on the result until the
#: harness SIGKILLs the process mid-flight.  Slow launches make the
#: kill window wide; the scores they produce stay bit-exact.
_DRILL_CHILD_SRC = """
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, {root!r})
sys.path.insert(0, {tools!r})
import jax
jax.config.update("jax_platforms", "cpu")
import spark_sklearn_tpu as sst
from sst_soak import _drill_data, _make_search
X, y = _drill_data()
cfg = sst.TpuConfig(
    tenant="drill", service_journal_dir={jdir!r},
    checkpoint_dir={cdir!r}, max_tasks_per_batch=4,
    telemetry_port=0,
    fault_plan=",".join("slow@%d:0.4" % i for i in range(1, 9)))
sess = sst.createLocalTpuSession("crash-drill-child", cfg)
search = _make_search(sst, cfg, 0)
fut = sess.submit(search, X, y)
print("SUBMITTED", flush=True)
fut.result()
print("FINISHED", flush=True)
"""


def _count_chunk_records(checkpoint_dir: str) -> int:
    """Completed-chunk records durably on disk across every search
    journal in ``checkpoint_dir`` (fault/meta lines don't count)."""
    import glob
    n = 0
    for path in glob.glob(os.path.join(checkpoint_dir,
                                       "search_*.jsonl")):
        try:
            with open(path, errors="replace") as f:
                for line in f:
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if "chunk_id" in rec:
                        n += 1
        except OSError:
            continue
    return n


def run_crash_drill(verbose: bool = True,
                    kill_timeout_s: float = 90.0) -> Dict[str, Any]:
    """The crash-safety arc, end to end: a child process journals a
    search and dies by ``kill -9`` once at least one checkpoint chunk
    is durable; the harness then fences the dead owner's lease,
    recovers through :meth:`TpuSession.recover` / ``resubmit()``, and
    asserts bit-exactness against the uncrashed baseline plus the
    crash-marker bundle, recovery telemetry, and an empty non-terminal
    set afterwards."""
    import glob
    import signal
    import subprocess
    import tempfile

    import numpy as np
    import spark_sklearn_tpu as sst
    from spark_sklearn_tpu.obs import telemetry as _telemetry
    from spark_sklearn_tpu.serve.journal import ServiceJournal

    def say(msg: str) -> None:
        if verbose:
            print(f"[crash-drill] {msg}", flush=True)

    failures: List[str] = []
    workdir = tempfile.mkdtemp(prefix="sst-crash-drill-")
    jdir = os.path.join(workdir, "journal")
    cdir = os.path.join(workdir, "ckpt")
    log_path = os.path.join(workdir, "child.log")

    # 1. the uncrashed baseline: same search, same data, no journal,
    # no checkpoints, no faults
    say("uncrashed baseline fit")
    X, y = _drill_data()
    solo = _make_search(sst, None, 0)
    solo.fit(X, y)
    baseline = solo.cv_results_["mean_test_score"].copy()

    # 2. the victim: journal + checkpoint in a child process, then
    # SIGKILL it the moment one chunk record is durable — mid-search
    # by construction (the brownout plan stretches the remainder)
    say(f"spawning victim child (journal={jdir})")
    child_src = _DRILL_CHILD_SRC.format(
        root=_ROOT, tools=os.path.join(_ROOT, "tools"),
        jdir=jdir, cdir=cdir)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    with open(log_path, "w") as log:
        child = subprocess.Popen([sys.executable, "-c", child_src],
                                 stdout=log,
                                 stderr=subprocess.STDOUT, env=env)
        deadline = time.monotonic() + kill_timeout_s
        n_chunks = 0
        while time.monotonic() < deadline:
            if child.poll() is not None:
                break
            n_chunks = _count_chunk_records(cdir)
            if n_chunks >= 1:
                break
            time.sleep(0.05)
        if child.poll() is not None:
            with open(log_path, errors="replace") as f:
                tail = f.read()[-2000:]
            failures.append(
                f"victim exited rc={child.returncode} before the "
                f"kill landed; output tail: {tail!r}")
        elif n_chunks < 1:
            child.kill()
            failures.append(
                f"no durable chunk record within {kill_timeout_s}s "
                "— nothing to kill mid-flight")
        else:
            say(f"SIGKILL pid={child.pid} after {n_chunks} durable "
                "chunk record(s)")
            os.kill(child.pid, signal.SIGKILL)
        child.wait()

    if failures:
        return {"failures": failures, "workdir": workdir}

    # 3. the survivor: fence the dead owner's lease, recover the
    # journaled search, resubmit against the same data
    say("recovery session: fence + recover + resubmit")
    rcfg = sst.TpuConfig(tenant="drill", service_journal_dir=jdir,
                         checkpoint_dir=cdir, max_tasks_per_batch=4,
                         telemetry_port=0)
    t_recover0 = time.perf_counter()
    sess = sst.createLocalTpuSession("crash-drill-recover", rcfg)
    time_to_recover_s = None
    try:
        report = sess.recover()
        if not report.taken_over:
            failures.append("dead owner's lease was not fenced "
                            "(RecoveryReport.taken_over is False)")
        if report.n_nonterminal != 1:
            failures.append(
                f"expected exactly 1 non-terminal journal entry, "
                f"found {report.n_nonterminal}")
        else:
            entry = report.entries[0]
            say(f"recovering {entry.handle} "
                f"(state={entry.state}, ckpt={entry.checkpoint_dir})")
            search2 = _make_search(sst, rcfg, 0)
            fut = sess.resubmit(entry, search2, X, y)
            fut.result()
            time_to_recover_s = time.perf_counter() - t_recover0
            scores = search2.cv_results_["mean_test_score"]
            if not np.array_equal(scores, baseline):
                failures.append(
                    "recovered cv_results_ diverged from the "
                    f"uncrashed baseline: {scores.tolist()} vs "
                    f"{baseline.tolist()}")
            else:
                say(f"recovered bit-exact in {time_to_recover_s:.2f}s")
        markers = glob.glob(os.path.join(jdir,
                                         "flight-crash-marker-*.json"))
        if not markers:
            failures.append("no crash-marker flight bundle landed in "
                            "the journal directory")
        snap = _telemetry.get_telemetry().snapshot()
        rec_block = (snap or {}).get("recovery") or {}
        if not rec_block.get("recovered_total"):
            failures.append("telemetry recovery block shows zero "
                            f"recovered_total: {rec_block}")
        if not rec_block.get("lease_takeovers_total"):
            failures.append("telemetry recovery block shows zero "
                            f"lease_takeovers_total: {rec_block}")
    finally:
        sess.stop()

    # 4. the ledger after the dust settles: the journal owes nothing
    post = ServiceJournal(jdir).nonterminal()
    if post:
        failures.append(
            f"journal still owes {sorted(post)} after recovery")

    result = {
        "failures": failures,
        "workdir": workdir,
        "n_chunks_at_kill": n_chunks,
        "time_to_recover_s": (round(time_to_recover_s, 3)
                              if time_to_recover_s is not None
                              else None),
    }
    if failures:
        for f in failures:
            say(f"FAILURE: {f}")
    else:
        say("CRASH DRILL GREEN: killed mid-search, lease fenced, "
            "recovered bit-exact, journal owes nothing")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tenants", type=int, default=2)
    ap.add_argument("--searches", type=int, default=3,
                    help="searches per tenant in the main wave")
    ap.add_argument("--plan", default=DEFAULT_PLAN,
                    help="chaos plan (fault tokens + session events)")
    ap.add_argument("--deadline", type=float, default=120.0,
                    help="per-search search_deadline_s")
    ap.add_argument("--max-p95", type=float, default=60.0,
                    help="queue-wait p95 bound (seconds)")
    ap.add_argument("--quarantine-k", type=int, default=2)
    ap.add_argument("--launch-timeout", type=float, default=20.0)
    ap.add_argument("--crash-drill", action="store_true",
                    help="run the kill -9 crash-recovery drill "
                         "instead of the chaos soak")
    ap.add_argument("--json", action="store_true",
                    help="emit the full soak ledger as JSON")
    args = ap.parse_args(argv)
    if args.crash_drill:
        result = run_crash_drill(verbose=not args.json)
        if args.json:
            print(json.dumps(result, indent=2, default=str))
        return 1 if result["failures"] else 0
    if args.tenants < 2:
        ap.error("a soak needs >= 2 tenants")
    result = run_soak(n_tenants=args.tenants,
                      n_searches=args.searches, plan=args.plan,
                      deadline_s=args.deadline,
                      max_p95_s=args.max_p95,
                      quarantine_k=args.quarantine_k,
                      launch_timeout_s=args.launch_timeout,
                      verbose=not args.json)
    if args.json:
        print(json.dumps(result, indent=2, default=str))
    return 1 if result["failures"] else 0


if __name__ == "__main__":
    sys.exit(main())
