"""Headline benchmark — BASELINE.json north star, with MFU accounting.

Legs (TPU platform):
  1. headline: 1000-candidate x 5-fold LogisticRegression grid on sklearn
     digits (BASELINE config #1 at north-star candidate count) — fp32
     warm/cold + bf16, with achieved GFLOP/s and %-of-bf16-peak derived
     from the solver's executed iteration counts.  digits is
     latency-bound by design (64 features) — the MFU figure documents
     that honestly rather than hiding it.
  2. svc_mxu: BASELINE config #2 shape — SVC(rbf) C x gamma grid on a
     synthetic MNIST-shaped binary dataset (10k x 784).  Dominated by
     (10k, 784) @ (784, 10k) kernel builds — real MXU work with
     analytically exact FLOP counts.
  3. digits SVC, BASELINE configs #3-#5 stand-ins, keyed fleet leg.

Baseline side: serial sklearn fits (the per-task work the reference fans
out to Spark executors), measured on a candidate subsample and scaled
linearly; divided by 8 as an *ideal* 8-executor Spark-CPU proxy (zero
scheduling/broadcast overhead — strictly favourable to the baseline).

Output contract: prints one JSON result line per milestone, each line a
complete payload superseding the previous one; the driver (and
`_parse_last_json_line`) take the LAST parseable line.  Lines are
flushed immediately, so a timeout kill still leaves the best-known
result in the captured stdout.

Robustness (round-3 postmortem: the driver recorded rc=124 with EMPTY
stdout because the old design probed the backend for up to ~41 min
before doing anything else, and printed only at the very end):
  * The top-level orchestrator never imports jax, so it cannot hang
    inside backend init, and it never holds the accelerator its
    children need (a chip belongs to one process at a time).
  * Hard total budget (BENCH_TOTAL_BUDGET_S, default 19 min) enforced
    by SIGALRM; SIGTERM/SIGINT/SIGALRM handlers flush the best-known
    payload and kill any live child, so even a harness kill yields a
    parseable line.
  * Order: ONE quick backend probe (60 s) in a throwaway child -> if it
    reports an accelerator, full run with the remaining budget;
    otherwise CPU smoke FIRST (emits its line within ~6 min), then
    probe retries in whatever budget remains, emitting a superseding
    line on success.
  * Children emit progressively (after the headline and after every
    leg), and the orchestrator parses partial stdout even on child
    timeout/nonzero rc — a slow leg can no longer erase the headline.

This file is rewritten by the benchmark PR that follows PR 21; until
then its CPU fallback, assumed-peak default and exit-0 handlers remain,
so a line it prints is a device figure only when its "platform" says
so.  The chip is reached through the chip tool running chip_smoke.py.
"""

import json
import os
import signal
import subprocess
import sys
import time

_PROBE_CODE = """
import os
import time
if os.environ.get("BENCH_FAKE_WEDGE") == "1":
    time.sleep(3600)   # test hook: reproduce the wedge signature (hang)
import json
import jax
d = jax.devices()[0]
print(json.dumps({"platform": d.platform, "n_devices": len(jax.devices()),
                  "device_kind": getattr(d, "device_kind", "")}))
"""

#: hard wall for the whole orchestration — must undercut the driver's
#: own timeout (round 3's was evidently < ~40 min; round 2's successful
#: run fit in well under 20).
TOTAL_BUDGET_S = int(os.environ.get("BENCH_TOTAL_BUDGET_S", "1140"))
PROBE_TIMEOUT_S = int(os.environ.get("BENCH_PROBE_TIMEOUT_S", "60"))
PROBE_RETRY_SLEEP_S = int(os.environ.get("BENCH_PROBE_RETRY_SLEEP_S", "45"))
CPU_CHILD_TIMEOUT_S = int(os.environ.get("BENCH_CPU_CHILD_TIMEOUT_S", "600"))
#: don't bother starting a TPU child with less runway than this — the
#: headline leg alone (compile + 2 fits + serial baseline) needs ~3 min.
TPU_MIN_RUN_S = int(os.environ.get("BENCH_TPU_MIN_RUN_S", "180"))

#: dense bf16 peak by device kind — the MFU denominator.  fp32 matmuls
#: lower to multi-pass bf16 on this hardware, so fp32 legs are reported
#: against the same bf16 peak (documented, not hidden).  Unknown kinds
#: fall back to the v5e figure WITH the assumption recorded in detail.
_PEAK_BF16_BY_KIND = [
    ("TPU v6", 918e12),      # v6e / Trillium
    ("TPU v5p", 459e12),
    ("TPU v5 lite", 197e12),  # v5e — this machine's chip
    ("TPU v5e", 197e12),
    ("TPU v4", 275e12),
]
_DEFAULT_PEAK = ("TPU v5e (assumed)", 197e12)


def _peak_bf16_flops(device_kind):
    """(label, peak FLOP/s) for the MFU denominator; prefix-matched so
    'TPU v5 lite0' resolves.  ADVICE r3: record the assumption instead
    of silently hard-coding v5e."""
    for prefix, peak in _PEAK_BF16_BY_KIND:
        if device_kind.startswith(prefix):
            return device_kind, peak
    return _DEFAULT_PEAK


# --------------------------------------------------------------------------
# Orchestrator (never imports jax)
# --------------------------------------------------------------------------

_LIVE_CHILD = None      # Popen of the currently-running child, if any
_EMITTED_ANY = False    # once True, stdout already holds a parseable line


def _emit(payload):
    global _EMITTED_ANY
    _EMITTED_ANY = True
    print(json.dumps(payload), flush=True)


def _flush_and_die(signum, frame):
    """SIGTERM/SIGALRM/SIGINT: make sure SOMETHING parseable is on
    stdout, kill any live child, exit 0 so the driver parses the tail."""
    if not _EMITTED_ANY:
        print(json.dumps({
            "metric": "GridSearchCV LogReg digits — fits/sec "
                      "(speedup vs ideal 8-exec Spark-CPU proxy)",
            "value": 0.0, "unit": "fits/sec", "vs_baseline": 0.0,
            "platform": "none",
            "error": f"terminated by signal {signum} before any "
                     "measurement completed",
        }), flush=True)
    try:
        if _LIVE_CHILD is not None and _LIVE_CHILD.poll() is None:
            _LIVE_CHILD.kill()
    except OSError:
        pass
    os._exit(0)


def _run_child_process(args, timeout_s, env=None):
    """subprocess.run equivalent that tracks the live child for the
    signal handler and returns (rc, stdout, stderr) even on timeout —
    partial stdout matters (children emit progressively)."""
    global _LIVE_CHILD
    proc = subprocess.Popen(args, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True, env=env)
    _LIVE_CHILD = proc
    try:
        out, err = proc.communicate(timeout=timeout_s)
        return proc.returncode, out, err
    except subprocess.TimeoutExpired:
        proc.kill()
        out, err = proc.communicate()
        return "timeout", out or "", err or ""
    finally:
        _LIVE_CHILD = None


def _probe_tpu_once(timeout_s=None):
    """One throwaway-subprocess check whether a non-CPU backend comes up."""
    rc, out, _ = _run_child_process(
        [sys.executable, "-c", _PROBE_CODE], timeout_s or PROBE_TIMEOUT_S)
    if rc == "timeout":
        return None, "probe-timeout"
    if rc != 0:
        return None, f"probe-rc-{rc}"
    try:
        info = json.loads(out.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return None, "probe-unparseable"
    if info.get("platform") in (None, "cpu"):
        return None, f"probe-platform-{info.get('platform')}"
    return info, "ok"


def _parse_last_json_line(stdout):
    """Last stdout line that parses as a JSON object (a stray trailing
    print from a library must not masquerade as the benchmark result)."""
    for line in reversed(stdout.strip().splitlines()):
        try:
            out = json.loads(line)
        except ValueError:
            continue
        if isinstance(out, dict):
            return out
    return None


def _try_tpu_run(timeout_s, probe_attempts):
    """Run the TPU child; emit its (possibly partial) last payload.
    Returns True if a TPU result line was emitted."""
    rc, out, err = _run_child_process(
        [sys.executable, __file__, "--child", "tpu"], timeout_s)
    sys.stderr.write(err[-4000:])
    payload = _parse_last_json_line(out)
    if payload is not None and payload.get("platform") not in (
            None, "cpu", "cpu-fallback"):
        payload["tpu_probe_attempts"] = probe_attempts
        if rc != 0:
            payload["partial"] = f"tpu child rc={rc}; last milestone kept"
        _emit(payload)
        return True
    if payload is not None and payload.get("platform") == "cpu-fallback" \
            and not _EMITTED_ANY:
        # the claim was lost between probe and backend init and the child
        # completed the scaled-down smoke on CPU — a valid fallback
        # measurement: emit it (a later TPU line supersedes), and the
        # orchestrator's own CPU smoke becomes redundant
        payload["tpu_probe_attempts"] = list(probe_attempts)
        payload["note2"] = "measured by the TPU child after losing the chip"
        _emit(payload)
    probe_attempts.append({"tpu_child_rc": rc, "stderr_tail": err[-400:]})
    return False


def orchestrate():
    for sig in (signal.SIGTERM, signal.SIGINT, signal.SIGALRM):
        signal.signal(sig, _flush_and_die)
    signal.alarm(TOTAL_BUDGET_S)
    # readiness marker for tests: a SIGTERM landing before this line
    # hits the default disposition
    print("bench: signal handlers installed", file=sys.stderr, flush=True)
    t0 = time.time()

    def remaining():
        return TOTAL_BUDGET_S - (time.time() - t0)

    probe_attempts = []

    def probe(timeout_s=None):
        info, status = _probe_tpu_once(timeout_s)
        probe_attempts.append(
            {"t_offset_s": round(time.time() - t0), "status": status})
        return info, status

    # --- phase 1: ONE quick probe; healthy chip -> TPU-first ------------
    skip_cpu = os.environ.get("BENCH_SKIP_CPU_SMOKE") == "1"
    info, status = probe()
    if info is not None:
        if _try_tpu_run(max(remaining() - 30, 60), probe_attempts):
            return 0

    # --- phase 2: CPU smoke — guarantees a parseable line early ---------
    # (skipped when a lost-claim TPU child already measured it above)
    if not skip_cpu and not _EMITTED_ANY:
        env = dict(os.environ)
        # the child also sets jax.config, in case jax was imported
        # before the env var could take effect
        env["JAX_PLATFORMS"] = "cpu"
        rc, out, err = _run_child_process(
            [sys.executable, __file__, "--child", "cpu"],
            min(CPU_CHILD_TIMEOUT_S, max(remaining() - TPU_MIN_RUN_S, 120)),
            env=env)
        sys.stderr.write(err[-4000:])
        payload = _parse_last_json_line(out)
        if payload is not None:
            payload["tpu_probe_attempts"] = list(probe_attempts)
            if rc != 0:
                payload["partial"] = f"cpu child rc={rc}; last milestone kept"
            _emit(payload)
        else:
            probe_attempts.append(
                {"cpu_child_rc": rc, "stderr_tail": err[-400:]})

    # --- phase 3: keep probing the chip with whatever budget remains ----
    # The claim has been observed to clear spontaneously mid-round; a
    # superseding TPU line is strictly better than the CPU smoke line.
    # Retries cover the wedge signature (probe hang) AND a transient
    # claim loss between a healthy probe and the TPU child's backend
    # init (status stays "ok" but the run yields no TPU line); a probe
    # that ANSWERS 'cpu' or crashes deterministically cannot change.
    while status in ("probe-timeout", "ok") \
            and remaining() > TPU_MIN_RUN_S + 90:
        time.sleep(min(PROBE_RETRY_SLEEP_S, max(remaining() / 4, 1)))
        info, status = probe(min(PROBE_TIMEOUT_S, remaining() - TPU_MIN_RUN_S))
        if info is not None and _try_tpu_run(
                max(remaining() - 20, 60), probe_attempts):
            break

    if not _EMITTED_ANY:
        _emit({
            "metric": "GridSearchCV LogReg digits — fits/sec "
                      "(speedup vs ideal 8-exec Spark-CPU proxy)",
            "value": 0.0, "unit": "fits/sec", "vs_baseline": 0.0,
            "platform": "none",
            "error": "all benchmark attempts failed",
            "attempts": probe_attempts,
        })
    return 0


# --------------------------------------------------------------------------
# Measurement legs — parameterized with injectable shapes so every leg is
# smoke-testable at toy size on the CPU mesh (VERDICT r3 weak #2: the
# TPU-only legs had never executed anywhere; their first run must not be
# inside the rare chip-unwedge window).
# --------------------------------------------------------------------------

def _glm_fit_flops(report, n, d, k):
    """Executed fit-phase matmul FLOPs from the engine's per-launch
    (iters, lanes) record.  One GLM L-BFGS iteration per lane = one
    forward Ax (2*n*d*k) + one backward AT (2*n*d*k); the +20%-ish
    line-search/elementwise work is excluded (MFU convention counts
    useful matmul FLOPs only)."""
    iters = report.get("solver_iters_per_launch", [])
    lanes = report.get("lanes_per_launch", [])
    il = sum(i * l for i, l in zip(iters, lanes))
    return 4.0 * n * d * max(k, 1) * il, (max(iters) if iters else 0)


def _faults_summary(report):
    """The search's recovery counters (search_report["faults"] minus the
    per-event journal) — recorded per leg so BENCH_* files show whether
    a number was achieved clean or paid recovery overhead."""
    f = dict(report.get("faults", {}))
    f.pop("events", None)
    return f


def _dataplane_summary(report):
    """The search's transfer counters (search_report["dataplane"]) plus
    the padding_waste histogram — recorded per leg so successive
    BENCH_r*.json files show the host->device byte trend and how much
    launch compute was padding."""
    dp = dict(report.get("dataplane", {}))
    out = {k: dp[k] for k in (
        "enabled", "hits", "misses", "bytes_uploaded", "bytes_tiled",
        "bytes_staged", "mask_tiling") if k in dp}
    pw = report.get("padding_waste")
    if pw:
        out["padding_waste"] = dict(pw)
    geo = report.get("geometry")
    if geo:
        out["geometry"] = {k: geo[k] for k in (
            "mode", "source", "planned_launches", "planned_waste_frac")
            if k in geo}
    return out


def _memory_summary(report):
    """The search's device-memory ledger view (search_report["memory"]
    minus the per-group series, which is summarized to its peak) —
    recorded per leg so BENCH_r*.json files show the modeled footprint
    trend and whether the HBM ceiling ever bound a width."""
    m = dict(report.get("memory", {}))
    if not m:
        return {}
    out = {k: m[k] for k in (
        "measured", "budget_bytes", "peak_modeled_bytes",
        "resident_bytes", "watermark_bytes", "model_error_frac",
        "safety_margin") if k in m}
    groups = m.get("groups") or []
    out["n_group_footprints"] = len(groups)
    out["n_capped_widths"] = sum(1 for g in groups if g.get("capped"))
    return out


def leg_sstlint():
    """Run the sstlint static-analysis gate in-process and record its
    cost (rule count, finding counts, wall) — the gate rides tier-1,
    so successive BENCH_r*.json files keep its price visible."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from tools.sstlint import run_lint

    res = run_lint(root=os.path.dirname(os.path.abspath(__file__)))
    # the declared-registry sizes ride along: a surface or record kind
    # silently dropping out of the registries shows up in the trend
    from spark_sklearn_tpu.utils import journalspec, keycheck
    return {"n_rules": res["n_rules"],
            "n_findings": res["n_findings"],
            "n_baselined": res["n_baselined"],
            "n_key_surfaces": len(keycheck.KEY_SURFACES),
            "n_journal_kinds": (len(journalspec.CHECKPOINT_RECORD_KINDS)
                                + len(journalspec.CHECKPOINT_META_KINDS)
                                + len(journalspec.SERVICE_RECORD_KINDS)),
            "duration_s": res["duration_s"]}


def leg_headline(cache_dir=None, n_candidates=1000, n_folds=5,
                 max_iter=100, measure_bf16=False, serial_subsample=20):
    """BASELINE config #1 at north-star scale: LogReg C-grid on digits.
    Returns (detail, fits_per_sec, vs_baseline)."""
    import numpy as np
    from sklearn.base import clone
    from sklearn.datasets import load_digits
    from sklearn.linear_model import LogisticRegression
    from sklearn.model_selection import StratifiedKFold

    import jax
    import spark_sklearn_tpu as sst

    X, y = load_digits(return_X_y=True)
    X = (X / 16.0).astype(np.float32)
    n_samples, n_feat = X.shape
    n_classes = 10

    grid = {"C": list(np.logspace(-4, 3, n_candidates))}
    est = LogisticRegression(max_iter=max_iter)
    cv = StratifiedKFold(n_splits=n_folds)
    n_fits = n_candidates * n_folds

    cache_cfg = sst.TpuConfig(compilation_cache_dir=cache_dir)
    gs = sst.GridSearchCV(est, grid, cv=cv, backend="tpu", refit=False,
                          config=cache_cfg)
    t0 = time.perf_counter()
    gs.fit(X, y)
    dev_cold = time.perf_counter() - t0

    # steady-state re-run: same program shapes -> compile cache hit
    gs2 = sst.GridSearchCV(est, grid, cv=cv, backend="tpu", refit=False,
                           config=cache_cfg)
    t0 = time.perf_counter()
    gs2.fit(X, y)
    dev_warm = time.perf_counter() - t0

    detail = {
        "wall_s_cold": round(dev_cold, 2),
        "wall_s_warm": round(dev_warm, 2),
        "n_fits": n_fits,
        "n_candidates": n_candidates,
        "best_mean_test_score": round(
            float(gs.cv_results_["mean_test_score"].max()), 4),
        # pipelined-executor timeline (stage/dispatch/compute/gather
        # walls + overlap fraction) for the cold and warm searches —
        # the observable for the chunk scheduler's host/device overlap
        "pipeline_cold": {
            k: v for k, v in gs.search_report.get(
                "pipeline", {}).items() if k != "launches"},
        "pipeline_warm": {
            k: v for k, v in gs2.search_report.get(
                "pipeline", {}).items() if k != "launches"},
        "faults": _faults_summary(gs2.search_report),
        # data-plane traffic: the cold search uploads, the warm search
        # must show hits and (near-)zero cacheable bytes — the transfer
        # trend future BENCH_r*.json compare against
        "dataplane_cold": _dataplane_summary(gs.search_report),
        "dataplane_warm": _dataplane_summary(gs2.search_report),
        # device-memory ledger view: the headline is the acceptance
        # leg, so an unpopulated ledger is a bug, not a shrug
        "memory_cold": _memory_summary(gs.search_report),
        "memory_warm": _memory_summary(gs2.search_report),
    }
    mem = gs2.search_report.get("memory") or {}
    assert mem.get("enabled") and mem.get("peak_modeled_bytes", 0) > 0 \
        and mem.get("groups"), f"memory ledger unpopulated: {mem}"

    # MFU accounting (honest: digits is latency-bound — 64 features
    # cannot fill the MXU; the number exists to quantify that, the
    # svc_mxu leg exists to show filled tiles).  Under the default fused
    # launch, fit_wall_s includes the (tiny) scoring epilogue, so the
    # reported MFU is a slight UNDERestimate of the fit-only figure.
    dev = jax.devices()[0]
    kind_label, peak = _peak_bf16_flops(getattr(dev, "device_kind", ""))
    rep = gs2.search_report
    glm_flops, glm_iters = _glm_fit_flops(rep, n_samples, n_feat, n_classes)
    if glm_flops and dev_warm > 0:
        fit_wall = rep.get("fit_wall_s", dev_warm) or dev_warm
        detail["headline_mfu"] = {
            "fit_matmul_gflops_total": round(glm_flops / 1e9, 1),
            "solver_iters_max": glm_iters,
            "fit_wall_s": round(fit_wall, 2),
            "achieved_gflops_per_s": round(glm_flops / fit_wall / 1e9, 1),
            "pct_of_bf16_peak": round(
                100.0 * glm_flops / fit_wall / peak, 3),
            "peak_denominator": {"device_kind": kind_label,
                                 "bf16_peak_tflops": round(peak / 1e12)},
            "note": "digits (d=64) is latency/bandwidth-bound by design; "
                    "see svc_mxu leg for an MXU-bound measurement",
        }

    if measure_bf16:
        # bf16 MXU variant (solver state fp32; oracle-tested parity ~1e-2)
        cfg16 = sst.TpuConfig(bf16_matmul=True, compile_cache_dir=cache_dir)
        sst.GridSearchCV(est, grid, cv=cv, backend="tpu", refit=False,
                         config=cfg16).fit(X, y)  # compile
        gs3 = sst.GridSearchCV(est, grid, cv=cv, backend="tpu", refit=False,
                               config=cfg16)
        t0 = time.perf_counter()
        gs3.fit(X, y)
        tpu_bf16 = time.perf_counter() - t0
        detail.update({
            "wall_s_bf16": round(tpu_bf16, 2),
            "bf16_fits_per_sec": round(n_fits / tpu_bf16, 2),
            "bf16_best_score": round(float(
                gs3.cv_results_["mean_test_score"].max()), 4),
        })

    # --- baseline side: serial sklearn per-task fits --------------------
    sub = min(serial_subsample, n_candidates)
    splits = list(cv.split(X, y))
    t0 = time.perf_counter()
    for C in np.logspace(-4, 3, sub):
        for train, test in splits:
            e = clone(est).set_params(C=float(C))
            e.fit(X[train], y[train])
            e.score(X[test], y[test])
    serial_sub = time.perf_counter() - t0
    serial_est = serial_sub * (n_candidates / sub)
    spark8_proxy = serial_est / 8.0
    detail["serial_sklearn_est_s"] = round(serial_est, 1)
    detail["spark8_ideal_proxy_s"] = round(spark8_proxy, 1)
    if measure_bf16:
        detail["bf16_vs_baseline"] = round(spark8_proxy / tpu_bf16, 2)

    # headline stays fp32 so numbers are comparable across configs and
    # against the fp64 sklearn baseline; bf16 reported separately
    return detail, n_fits / dev_warm, spark8_proxy / dev_warm


def leg_svc_mxu(cache_dir=None, n=10_000, d=784, folds=3, max_iter=100,
                C_values=(0.1, 1.0, 10.0, 100.0), gamma_values=(1e-3, 1e-2)):
    """BASELINE config #2 shape — SVC(rbf) C x gamma on a synthetic
    MNIST-shaped BINARY problem: kernel builds are (n, d) @ (d, n) —
    exactly countable MXU FLOPs."""
    import numpy as np
    from sklearn.svm import SVC

    import jax
    import spark_sklearn_tpu as sst

    rng = np.random.RandomState(0)
    Xs = rng.randn(n, d).astype(np.float32)
    ys = (Xs[:, :min(16, d)].sum(axis=1) > 0).astype(np.int32)
    svc_grid = {"C": list(C_values), "gamma": list(gamma_values)}
    n_cand = len(C_values) * len(gamma_values)
    cfg = sst.TpuConfig(compile_cache_dir=cache_dir)
    svc = sst.GridSearchCV(SVC(max_iter=max_iter), svc_grid, cv=folds,
                           refit=False, backend="tpu", config=cfg)
    t0 = time.perf_counter()
    svc.fit(Xs, ys)
    svc_wall = time.perf_counter() - t0
    # per candidate: kernel 2*n^2*d; power-step 40*n^2; dual ascent +
    # decision (F*P + tiny) x (n, n) matmuls, P=1 binary.  The kernel IS
    # built once per candidate and shared across folds (models/svm.py).
    # Dual term: since round 4 each candidate's solve exits at libsvm's
    # eps, so EXECUTED iterations come from the engine's per-lane record
    # (sum semantics — the scan runs candidates sequentially, each at
    # its own count); the max_iter formula remains only as the fallback
    # upper bound and is labelled as such in the detail.
    rep = svc.search_report
    sum_lane_iters = sum(rep.get("solver_iters_sum_per_launch", []))
    base_flops = (2.0 * n * n * d + 40.0 * n * n) * n_cand
    if sum_lane_iters > 0:
        # one lane = (candidate, fold); per lane per iteration one
        # (P, n) @ (n, n) matmul, P=1 binary; +1 decision pass per lane
        dual_flops = 2.0 * n * n * (sum_lane_iters + n_cand * folds)
        dual_note = "executed (per-candidate tol-exit counts)"
    else:
        dual_flops = 2.0 * folds * n * n * (max_iter + 1) * n_cand
        dual_note = "upper bound (no executed-iteration record)"
    svc_flops = base_flops + dual_flops
    dev = jax.devices()[0]
    kind_label, peak = _peak_bf16_flops(getattr(dev, "device_kind", ""))
    return {
        "shape": f"{n}x{d} binary, {n_cand} cand x {folds} folds, "
                 f"max_iter={max_iter}",
        "wall_s": round(svc_wall, 2),
        "fits_per_sec": round(n_cand * folds / svc_wall, 2),
        "kernel_tflops_total": round(svc_flops / 1e12, 9),
        "dual_flops_basis": dual_note,
        "achieved_gflops_per_s": round(svc_flops / svc_wall / 1e9, 1),
        "pct_of_bf16_peak": round(100.0 * svc_flops / svc_wall / peak, 2),
        "peak_denominator": {"device_kind": kind_label,
                             "bf16_peak_tflops": round(peak / 1e12)},
        "best_score": round(float(
            svc.cv_results_["mean_test_score"].max()), 4),
        "faults": _faults_summary(rep),
        "dataplane": _dataplane_summary(rep),
        "memory": _memory_summary(rep),
    }


def leg_svc_digits(cache_dir=None, n_C=8, n_gamma=8, folds=3,
                   n_rows=None):
    """Real-data sanity twin: SVC(rbf) C x gamma grid on digits.
    n_rows subsamples the dataset (test-toy sizing; None = all 1797)."""
    import numpy as np
    from sklearn.datasets import load_digits
    from sklearn.svm import SVC

    import spark_sklearn_tpu as sst

    X, y = load_digits(return_X_y=True)
    X = (X / 16.0).astype(np.float32)
    if n_rows is not None:
        X, y = X[:n_rows], y[:n_rows]
    svc_grid = {"C": list(np.logspace(-1, 2, n_C)),
                "gamma": list(np.logspace(-3, 0, n_gamma))}
    cfg = sst.TpuConfig(compile_cache_dir=cache_dir)
    svc = sst.GridSearchCV(SVC(), svc_grid, cv=folds, refit=False,
                           backend="tpu", config=cfg)
    t0 = time.perf_counter()
    svc.fit(X, y)
    w = time.perf_counter() - t0
    n_fits = n_C * n_gamma * folds
    return {"wall_s": round(w, 2),
            "fits_per_sec": round(n_fits / w, 2),
            "best_score": round(float(
                svc.cv_results_["mean_test_score"].max()), 4),
            "faults": _faults_summary(svc.search_report),
            "dataplane": _dataplane_summary(svc.search_report),
            "memory": _memory_summary(svc.search_report)}


def leg_config3_rf(cache_dir=None, n=20_000, d=54, n_classes=7, n_iter=8,
                   folds=3, est_lo=20, est_hi=60, depth_lo=4, depth_hi=9):
    """BASELINE config #3: RandomizedSearchCV over RandomForestClassifier
    on a covtype-shaped synthetic (real covtype needs network access)."""
    import numpy as np
    from scipy.stats import randint
    from sklearn.ensemble import RandomForestClassifier

    import spark_sklearn_tpu as sst

    rng = np.random.RandomState(1)
    Xc = rng.randn(n, d).astype(np.float32)
    yc = rng.randint(0, n_classes, size=n)
    cfg = sst.TpuConfig(compile_cache_dir=cache_dir)
    rs = sst.RandomizedSearchCV(
        RandomForestClassifier(random_state=0),
        {"n_estimators": randint(est_lo, est_hi),
         "max_depth": randint(depth_lo, depth_hi)},
        n_iter=n_iter, cv=folds, random_state=0, refit=False,
        backend="tpu", config=cfg)
    t0 = time.perf_counter()
    rs.fit(Xc, yc)
    w = time.perf_counter() - t0
    return {"shape": f"{n}x{d} (covtype-shaped), {n_iter} iter x "
                     f"{folds} folds",
            "wall_s": round(w, 2),
            "fits_per_sec": round(n_iter * folds / w, 2),
            "backend": rs.search_report["backend"],
            "faults": _faults_summary(rs.search_report),
            "dataplane": _dataplane_summary(rs.search_report),
            "memory": _memory_summary(rs.search_report)}


def leg_config4_gbr(cache_dir=None, n=20_000, d=8, folds=3,
                    learning_rates=(0.05, 0.1), n_estimators=(50, 100)):
    """BASELINE config #4: GradientBoostingRegressor grid on a
    California-Housing-shaped synthetic (regression scorer path)."""
    import numpy as np
    from sklearn.ensemble import GradientBoostingRegressor

    import spark_sklearn_tpu as sst

    rng = np.random.RandomState(2)
    Xh = rng.randn(n, d).astype(np.float32)
    yh = (Xh[:, 0] * 2 + Xh[:, 1] ** 2
          + 0.3 * rng.randn(n)).astype(np.float32)
    cfg = sst.TpuConfig(compile_cache_dir=cache_dir)
    gbr = sst.GridSearchCV(
        GradientBoostingRegressor(max_depth=3, random_state=0),
        {"learning_rate": list(learning_rates),
         "n_estimators": list(n_estimators)}, cv=folds, refit=False,
        backend="tpu", config=cfg)
    t0 = time.perf_counter()
    gbr.fit(Xh, yh)
    w = time.perf_counter() - t0
    n_fits = len(learning_rates) * len(n_estimators) * folds
    return {"shape": f"{n}x{d} (California-shaped), "
                     f"{n_fits // folds} cand x {folds} folds",
            "wall_s": round(w, 2),
            "fits_per_sec": round(n_fits / w, 2),
            "backend": gbr.search_report["backend"],
            "faults": _faults_summary(gbr.search_report),
            "dataplane": _dataplane_summary(gbr.search_report),
            "memory": _memory_summary(gbr.search_report)}


def leg_config5_mlp(cache_dir=None, hidden=64, max_iter=60, folds=3,
                    alphas=(1e-4, 1e-3, 1e-2, 1e-1)):
    """BASELINE config #5: Pipeline(StandardScaler + MLPClassifier) grid
    on digits — exercises clone()/set_params through a pipeline."""
    import numpy as np
    from sklearn.datasets import load_digits
    from sklearn.neural_network import MLPClassifier
    from sklearn.pipeline import Pipeline
    from sklearn.preprocessing import StandardScaler

    import spark_sklearn_tpu as sst

    X, y = load_digits(return_X_y=True)
    X = (X / 16.0).astype(np.float32)
    pipe = Pipeline([
        ("scale", StandardScaler()),
        ("mlp", MLPClassifier(hidden_layer_sizes=(hidden,),
                              max_iter=max_iter, random_state=0))])
    cfg = sst.TpuConfig(compile_cache_dir=cache_dir)
    mlp = sst.GridSearchCV(
        pipe, {"mlp__alpha": list(alphas)}, cv=folds,
        refit=False, backend="tpu", config=cfg)
    t0 = time.perf_counter()
    mlp.fit(X, y)
    w = time.perf_counter() - t0
    n_fits = len(alphas) * folds
    return {"shape": f"digits, {len(alphas)} alpha x {folds} folds",
            "wall_s": round(w, 2),
            "fits_per_sec": round(n_fits / w, 2),
            "backend": mlp.search_report["backend"],
            "faults": _faults_summary(mlp.search_report),
            "dataplane": _dataplane_summary(mlp.search_report),
            "memory": _memory_summary(mlp.search_report)}


#: tiny search run by the persistent-cache/program-store probe
#: subprocesses: shapes deliberately distinct from every other leg so
#: the FIRST probe run compiles-and-publishes and LATER (fresh)
#: processes must hit.  argv: cache_dir store_dir manifest mode
#: (mode "cold" also re-fits in-process for the warm leg and writes the
#: prewarm manifest; mode "prewarmed" loads it at session init).
#: Always pinned to CPU — probing the cache machinery must never spawn
#: an extra process fighting for the TPU claim (round-1 postmortem).
_CACHE_PROBE_CODE = """
import json, sys, time
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from sklearn.datasets import load_digits
from sklearn.linear_model import LogisticRegression
import spark_sklearn_tpu as sst
cache_dir, store_dir, manifest, mode = sys.argv[1:5]
X, y = load_digits(return_X_y=True)
X = (X[:242] / 16.0).astype(np.float32); y = y[:242]
cfg = sst.TpuConfig(compilation_cache_dir=cache_dir,
                    persistent_cache_min_compile_s=0.0,
                    program_store_dir=store_dir,
                    prewarm_manifest=manifest)
sess = sst.TpuSession(config=cfg, appName="bench-store-probe")


def leg():
    gs = sst.GridSearchCV(LogisticRegression(max_iter=7),
                          {"C": [0.5, 2.0]}, cv=2, backend="tpu",
                          refit=False, config=cfg)
    t0 = time.perf_counter()
    gs.fit(X, y)
    wall = time.perf_counter() - t0
    pl = dict(gs.search_report["pipeline"])
    ps = gs.search_report["programstore"]
    return {"wall_s": round(wall, 2),
            "n_compiles": pl.get("n_compiles"),
            "persistent_cache_hits": pl.get("persistent_cache_hits"),
            "persistent_cache_misses": pl.get("persistent_cache_misses"),
            "store_hits": ps["hits"], "store_misses": ps["misses"],
            "store_publishes": ps["publishes"],
            "store_bytes_loaded": ps["bytes_loaded"],
            "store_prewarmed": ps["prewarmed"],
            # cumulative: manifest-prewarm IO lands before the search's
            # delta window, so the process total is the honest figure
            "store_bytes_loaded_process":
                sess.programstore_stats().get("bytes_loaded", 0)}


out = {mode: leg()}
if mode == "cold":
    # same process again: the in-process program cache serves every
    # program — the warm wall the prewarmed cold process is chasing
    out["warm"] = leg()
    sess.write_prewarm_manifest(manifest)
print(json.dumps(out))
"""


def leg_cache_probe(cache_dir, store_dir=None, timeout_s=240):
    """Cold/prewarmed/warm triple over the persistent caches.  Process
    A runs cold against an empty program store (publishing artifacts +
    the geometry plan state, writing the prewarm manifest) and re-fits
    in-process for the warm leg; process B — just as cold — runs
    against the populated store with manifest prewarm and must record
    store hits covering every compile group (`n_compiles == 0`), the
    zero-cold-start contract: its wall chases the warm leg's, not the
    cold one's."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    if store_dir is None:
        store_dir = os.path.join(cache_dir, "programstore")
    manifest = os.path.join(store_dir, "prewarm_manifest.json")
    out = {}
    for mode in ("cold", "prewarmed"):
        rc, stdout, err = _run_child_process(
            [sys.executable, "-c", _CACHE_PROBE_CODE, cache_dir,
             store_dir, manifest, mode], timeout_s, env=env)
        payload = _parse_last_json_line(stdout)
        if payload is None:
            out[mode] = {"error": f"rc={rc}; {err[-200:]}"}
        else:
            out.update(payload)
    cold_w = out.get("cold", {}).get("wall_s")
    warm_w = out.get("warm", {}).get("wall_s")
    pre_w = out.get("prewarmed", {}).get("wall_s")
    if cold_w and warm_w and pre_w:
        # the acceptance observable: how much of the cold-start wall the
        # store recovered (1.0 = prewarmed process as fast as warm)
        denom = cold_w - warm_w
        out["cold_start_recovered_frac"] = round(
            (cold_w - pre_w) / denom, 3) if denom > 0 else None
    return out


def leg_keyed(cache_dir=None, n_keys=1000, rows=20, d=8):
    """Keyed fleet breadth: n_keys per-key LinearRegression models.
    (cache_dir accepted for leg-signature uniformity; the keyed path
    manages its own programs.)"""
    import numpy as np
    import pandas as pd
    from sklearn.linear_model import LinearRegression

    import spark_sklearn_tpu as sst

    rng = np.random.RandomState(0)
    df = pd.DataFrame({
        "k": np.repeat(np.arange(n_keys), rows),
        "x": list(rng.randn(n_keys * rows, d).astype(np.float32)),
        "y": rng.randn(n_keys * rows).astype(np.float32)})
    t0 = time.perf_counter()
    km = sst.KeyedEstimator(
        sklearnEstimator=LinearRegression(), keyCols=["k"],
        xCol="x", yCol="y").fit(df)
    w = time.perf_counter() - t0
    return {"wall_s": round(w, 2),
            "models_per_sec": round(n_keys / w, 2),
            "backend": km.backend}


def leg_serve_contended(cache_dir=None, n_rows=242, n_candidates=48,
                        folds=2, max_iter=10, levels=(2, 4)):
    """Contended multi-tenant throughput: one TpuSession, `k`
    concurrent identical-shape searches per level — each under its OWN
    tenant — measuring aggregate searches/minute and the fair-share
    queue-wait distribution both in aggregate and PER TENANT (p50/p95
    from the scheduler block's tenant-stamped wait sample).  A solo
    run first warms every program, so the contended levels measure
    scheduling, not compilation.  Telemetry is on for the session, so
    each level also records its admission ledger (admitted / deferred
    / rejected deltas) and the protection-actuation counters.

    Cross-search launch fusion rides the main session (identical-shape
    tenants coalesce into wide launches), so each level also records
    the fusion ledger — fused dispatches, launches saved, the lane
    exchange, padded-lane waste — and a second ``fusion=False`` session
    replays every level as the A/B arm.  The searches/min ratio is the
    headline fusion win on lane-parallel devices; on a CPU host vmap
    lanes compute serially, so the expected A/B there is parity within
    noise while the ledger proves the coalescing (n_fused > 0, saved
    launches, zero padding regression)."""
    import numpy as np
    from sklearn.datasets import load_digits
    from sklearn.linear_model import LogisticRegression

    import spark_sklearn_tpu as sst
    from spark_sklearn_tpu.obs import telemetry as tel

    X, y = load_digits(return_X_y=True)
    X = (X[:n_rows] / 16.0).astype(np.float32)
    y = y[:n_rows]
    grid = {"C": np.logspace(-3, 2, n_candidates).tolist()}

    def search(tenant=None):
        # pinned chunk geometry (identical in both A/B arms): the
        # auto-planner re-tunes width per shape and box, which would
        # make the fused widths combination-dependent and the
        # searches/min trend column incomparable across rounds.  With
        # 16-lane chunks the session-wide width set is exactly
        # {16 solo, 32 fused} (fusion_max_width below).
        cfg = sst.TpuConfig(compilation_cache_dir=cache_dir,
                            tenant=tenant, max_tasks_per_batch=16)
        return sst.GridSearchCV(LogisticRegression(max_iter=max_iter),
                                grid, cv=folds, refit=False,
                                backend="tpu", config=cfg)

    def pct(sorted_vals, p):
        if not sorted_vals:
            return 0.0
        i = min(len(sorted_vals) - 1,
                int(round(p / 100.0 * (len(sorted_vals) - 1))))
        return round(sorted_vals[i], 6)

    def prot_counters():
        return tel.get_telemetry().snapshot()["protection"]

    def fuse_counters():
        return tel.get_telemetry().snapshot()["fusion"]

    # ephemeral-port telemetry: the admission/protection counters this
    # leg records are the ones tools/fleet_top.py renders in production.
    # fusion_window_ms=0 measures pure opportunistic coalescing —
    # already-queued peers fuse in the claim pass regardless of the
    # window, while a hold would tax peerless tail chunks with dead
    # time (on a CPU host that tax is unrecoverable: lanes compute
    # serially).  fusion_max_width pins fused launches to ONE doubling
    # of the solo width, so the warm pass compiles the single possible
    # fused program deterministically — unbounded member counts would
    # make the measured pass eat first-encounter compiles of
    # combination-dependent widths.
    sess = sst.createLocalTpuSession(
        "bench-serve", config=sst.TpuConfig(telemetry_port=0,
                                            fusion_window_ms=0.0,
                                            fusion_max_width=32))
    out = {"shape": f"digits[{n_rows}], {n_candidates} C x {folds} "
                    f"folds per search"}
    try:
        t0 = time.perf_counter()
        sess.submit(search(), X, y).result()
        out["solo_wall_s"] = round(time.perf_counter() - t0, 2)
        for k in levels:
            searches = [search(tenant=f"tenant{i}") for i in range(k)]
            # warm the COALESCED widths too: the solo warm-up only
            # compiled solo-width programs, and the measured pass must
            # capture scheduling, not the fused widths' first-encounter
            # compiles (the fusion-off arm's widths are already warm by
            # construction, so this keeps the A/B symmetric).  Two
            # passes, because which members coalesce varies run to run
            # and each distinct fused width is its own program.
            for _ in range(2):
                warm = [sess.submit(search(tenant=f"tenant{i}"), X, y)
                        for i in range(k)]
                for f in warm:
                    f.result()
            p0 = prot_counters()
            fu0 = fuse_counters()
            t0 = time.perf_counter()
            futs = [sess.submit(s, X, y) for s in searches]
            for f in futs:
                f.result()
            wall = time.perf_counter() - t0
            p1 = prot_counters()
            fu1 = fuse_counters()
            # per-tenant data-plane residency (DataPlane.tenant_usage_
            # all): the SLO view used to show queue-wait/throughput but
            # silently omit residency, leaving quota-pressure
            # starvation invisible.  Read before the next level's
            # searches re-charge the plane.
            tenant_resident = {
                str(t): int(b) for t, b in sorted(
                    sess.dataplane.tenant_usage_all().items())
            } if sess.dataplane is not None else {}
            # the waits sample is tenant-stamped (ISSUE 8 satellite),
            # so the merged distribution still attributes per tenant
            by_tenant = {}
            for s in searches:
                for w in s.search_report["scheduler"]["waits"]:
                    by_tenant.setdefault(w["tenant"], []).append(
                        w["wait_s"])
            waits = sorted(w for ws in by_tenant.values() for w in ws)
            interleave = [s.search_report["scheduler"]["interleave_frac"]
                          for s in searches]
            out[f"contended_{k}"] = {
                "wall_s": round(wall, 2),
                "searches_per_min": round(60.0 * k / wall, 2),
                "queue_wait_p50_s": pct(waits, 50),
                "queue_wait_p95_s": pct(waits, 95),
                "per_tenant_queue_wait": {
                    t: {"p50_s": pct(sorted(ws), 50),
                        "p95_s": pct(sorted(ws), 95),
                        "n": len(ws)}
                    for t, ws in sorted(by_tenant.items())},
                "interleave_frac": [round(f, 4) for f in interleave],
                "n_queue_waits": len(waits),
                "tenant_resident_bytes": tenant_resident,
                "admission": {
                    "admitted": p1["admitted_total"]
                    - p0["admitted_total"],
                    "deferred": p1["queued_total"]
                    - p0["queued_total"],
                    "rejected": p1["rejected_total"]
                    - p0["rejected_total"],
                },
                "protection": {
                    "shed": p1["shed_total"] - p0["shed_total"],
                    "quarantined": p1["quarantined_total"]
                    - p0["quarantined_total"],
                    "deadline_hits": p1["deadline_hits_total"]
                    - p0["deadline_hits_total"],
                    "declared_partial": sum(
                        1 for s in searches
                        if s.search_report.get(
                            "protection", {}).get("partial")),
                },
                # the fusion ledger: scheduler-block counters summed
                # over the level's searches, padded-lane waste from the
                # telemetry family delta (what the fused launches
                # actually burned over their real rows)
                "fusion": {
                    "n_fused": sum(
                        s.search_report["scheduler"].get("n_fused", 0)
                        for s in searches),
                    "saved_launches": sum(
                        s.search_report["scheduler"].get(
                            "fusion_saved_launches", 0)
                        for s in searches),
                    "lanes_donated": sum(
                        s.search_report["scheduler"].get(
                            "lanes_donated", 0) for s in searches),
                    "lanes_borrowed": sum(
                        s.search_report["scheduler"].get(
                            "lanes_borrowed", 0) for s in searches),
                    "padded_lane_waste": (
                        (fu1["lanes_padded_total"]
                         - fu1["lanes_real_total"])
                        - (fu0["lanes_padded_total"]
                           - fu0["lanes_real_total"])),
                },
            }
    finally:
        sess.stop()
    # the A/B arm: same shapes, same levels, fusion OFF — padding is
    # paid per search and every chunk launches alone, so the
    # searches/min ratio isolates what coalescing bought
    sess_off = sst.createLocalTpuSession(
        "bench-serve-nofuse",
        config=sst.TpuConfig(telemetry_port=0, fusion=False))
    try:
        sess_off.submit(search(), X, y).result()
        for k in levels:
            searches = [search(tenant=f"tenant{i}") for i in range(k)]
            t0 = time.perf_counter()
            futs = [sess_off.submit(s, X, y) for s in searches]
            for f in futs:
                f.result()
            wall = time.perf_counter() - t0
            blk = out[f"contended_{k}"]
            blk["fusion_off"] = {
                "wall_s": round(wall, 2),
                "searches_per_min": round(60.0 * k / wall, 2),
            }
            off = blk["fusion_off"]["searches_per_min"]
            blk["fusion_searches_per_min_ratio"] = round(
                blk["searches_per_min"] / off, 4) if off else None
    finally:
        sess_off.stop()
    # warm-restart cost (serve/journal.py): a journaled non-terminal
    # submission left behind by a "previous process" (stale dead-owner
    # lease) is recovered through TpuSession.recover()/resubmit().
    # time_to_recover_s is the telemetry gauge — journal scan at
    # session construction to the first successful re-admission — the
    # bench_trend watched column for restart-latency regressions.
    import shutil
    import tempfile

    from spark_sklearn_tpu.serve.journal import (ServiceJournal,
                                                 data_fingerprint)
    jdir = tempfile.mkdtemp(prefix="sst-bench-recover-")
    try:
        prev = ServiceJournal(jdir, owner="bench-previous")
        prev.record_submission(
            "bench/s1", tenant="bench", weight=1.0,
            family="LogisticRegression", structure_digest="bench",
            data_fingerprint=data_fingerprint(X, y))
        handle = prev.qualify("bench/s1")
        dead = subprocess.Popen([sys.executable, "-c", "pass"])
        dead.wait()
        with open(os.path.join(jdir, "service-lease.json"), "w") as f:
            json.dump({"pid": dead.pid, "owner": "bench-previous",
                       "ts_unix_s": time.time() - 3600,
                       "timeout_s": 30.0}, f)
        rsess = sst.createLocalTpuSession(
            "bench-serve-recover",
            config=sst.TpuConfig(service_journal_dir=jdir,
                                 telemetry_port=0))
        try:
            rsess.resubmit(handle, search(tenant="bench"), X,
                           y).result()
            rec = tel.get_telemetry().snapshot().get("recovery") or {}
            out["recovery"] = {
                "time_to_recover_s": rec.get("time_to_recover_s"),
                "recovered_total": rec.get("recovered_total"),
                "lease_takeovers_total": rec.get(
                    "lease_takeovers_total"),
            }
        finally:
            rsess.stop()
    finally:
        shutil.rmtree(jdir, ignore_errors=True)
    return out


def leg_halving(cache_dir=None, n_rows=484, n_candidates=96, folds=2,
                max_iter=25, factor=3):
    """Adaptive search (ISSUE 9): the SAME family + grid run
    exhaustively vs. successive halving at `factor`, WARM walls only
    (a throwaway first fit per arm compiles every program), recording
    the wall ratio, the per-rung candidate/width/lanes_reclaimed
    trajectory, and the replan-off control — which must produce
    byte-identical cv_results_ (lane reclamation is pure geometry)."""
    import numpy as np
    from sklearn.datasets import load_digits
    from sklearn.linear_model import LogisticRegression

    import spark_sklearn_tpu as sst

    X, y = load_digits(return_X_y=True)
    X = (X[:n_rows] / 16.0).astype(np.float32)
    y = y[:n_rows]
    grid = {"C": np.logspace(-4, 3, n_candidates).tolist()}

    def exhaustive():
        return sst.GridSearchCV(
            LogisticRegression(max_iter=max_iter), grid, cv=folds,
            refit=False, backend="tpu",
            config=sst.TpuConfig(compilation_cache_dir=cache_dir))

    def halving(**kw):
        return sst.HalvingGridSearchCV(
            LogisticRegression(max_iter=max_iter), grid, cv=folds,
            factor=factor, random_state=0, refit=False, backend="tpu",
            config=sst.TpuConfig(compilation_cache_dir=cache_dir, **kw))

    def timed(mk):
        mk().fit(X, y)                      # warm the programs
        t0 = time.perf_counter()
        gs = mk().fit(X, y)
        return gs, round(time.perf_counter() - t0, 3)

    ex, wall_ex = timed(exhaustive)
    on, wall_on = timed(halving)
    off, wall_off = timed(lambda: halving(halving_replan=False))
    hb = on.search_report["halving"]
    parity = all(
        np.array_equal(np.asarray(on.cv_results_[k]),
                       np.asarray(off.cv_results_[k]))
        for k in on.cv_results_ if "time" not in k and k != "params")
    return {
        "shape": f"digits[{n_rows}], {n_candidates} C x {folds} folds, "
                 f"factor={factor}",
        "exhaustive_warm_wall_s": wall_ex,
        "halving_warm_wall_s": wall_on,
        "halving_replan_off_warm_wall_s": wall_off,
        "wall_ratio_exhaustive_over_halving": round(
            wall_ex / wall_on, 3) if wall_on else 0.0,
        "n_fits_exhaustive": n_candidates * folds,
        "n_fits_halving": int(sum(on.n_candidates_)) * folds,
        # the budget metric halving actually optimizes: candidate x
        # resource units spent (halving's many extra fits are CHEAP —
        # rung row-compaction makes compute proportional to resource)
        "resource_units_exhaustive": int(
            n_candidates * on.max_resources_) * folds,
        "resource_units_halving": int(sum(
            nc * r for nc, r in zip(on.n_candidates_,
                                    on.n_resources_))) * folds,
        "n_rungs": hb["n_rungs"],
        "lanes_reclaimed_total": hb["lanes_reclaimed_total"],
        "rungs": [{k: r[k] for k in ("iter", "n_candidates",
                                     "n_resources", "widths",
                                     "lanes_reclaimed", "wall_s")}
                  for r in hb["rungs"]],
        "replan_off_cv_results_identical": bool(parity),
        "best_params_agree": bool(
            on.best_params_ == off.best_params_),
        "memory": _memory_summary(on.search_report),
    }


def leg_stream_sparse(cache_dir=None, n=4_000, d=512, density=0.01,
                      n_alphas=6, folds=3, budget_mib=4):
    """Out-of-core data tiers (ISSUE PR 15): the SAME NB grid run three
    ways — dense in-core, `data_mode="sparse"` (BCOO Tier-A), and a
    budget-constrained `data_mode="stream"` — recording the dense-vs-
    BCOO h2d bytes/wall/launches and the streamed plan (shard count,
    streamed h2d volume, zero-bisection completion under a budget the
    dense upload could never fit)."""
    import numpy as np
    import scipy.sparse as sp
    from sklearn.naive_bayes import MultinomialNB

    import spark_sklearn_tpu as sst
    from spark_sklearn_tpu.parallel import dataplane as _dataplane

    rng = np.random.default_rng(0)
    Xs = sp.random(n, d, density=density, format="csr", random_state=rng)
    Xs.data = np.ceil(Xs.data * 5).astype(np.float64)
    y = rng.integers(0, 3, size=n)
    grid = {"alpha": np.logspace(-2, 2, n_alphas).tolist()}

    def run(X, **cfg_kw):
        gs = sst.GridSearchCV(
            MultinomialNB(), grid, cv=folds, refit=False,
            backend="tpu",
            config=sst.TpuConfig(compilation_cache_dir=cache_dir,
                                 **cfg_kw))
        before = _dataplane.bytes_uploaded()
        t0 = time.perf_counter()
        gs.fit(X, y)
        return gs, round(time.perf_counter() - t0, 3), \
            int(_dataplane.bytes_uploaded() - before)

    dense_gs, dense_wall, dense_h2d = run(Xs.toarray())
    sparse_gs, sparse_wall, sparse_h2d = run(Xs, data_mode="sparse")
    stream_gs, stream_wall, stream_h2d = run(
        Xs.toarray(), data_mode="stream",
        hbm_budget_bytes=int(budget_mib * (1 << 20)),
        memory_ledger=True)
    blk = stream_gs.search_report["streaming"]
    agree = np.allclose(dense_gs.cv_results_["mean_test_score"],
                        sparse_gs.cv_results_["mean_test_score"],
                        atol=1e-6)
    return {
        "shape": f"{n}x{d} CSR @ {density:.0%} nnz, "
                 f"{n_alphas} alphas x {folds} folds",
        "dense_x_bytes": int(n * d * 4),
        "nnz_component_bytes": int(Xs.data.nbytes + Xs.indices.nbytes
                                   + Xs.indptr.nbytes),
        "dense_wall_s": dense_wall,
        "sparse_wall_s": sparse_wall,
        "stream_wall_s": stream_wall,
        "dense_h2d_bytes": dense_h2d,
        "sparse_h2d_bytes": sparse_h2d,
        "stream_h2d_bytes": stream_h2d,
        "sparse_over_dense_h2d": round(sparse_h2d / dense_h2d, 4)
        if dense_h2d else 0.0,
        "n_launches_dense": int(
            dense_gs.search_report.get("n_launches", 0)),
        "n_launches_sparse": int(
            sparse_gs.search_report.get("n_launches", 0)),
        "n_launches_stream": int(
            stream_gs.search_report.get("n_launches", 0)),
        "sparse_scores_match_dense": bool(agree),
        "stream_budget_mib": budget_mib,
        "stream_n_shards": blk["n_shards"],
        "stream_shard_rows": blk["shard_rows"],
        "stream_capped": blk["capped"],
        "stream_block_h2d_bytes": blk["h2d_bytes"],
        "stream_bisections": int(stream_gs.search_report.get(
            "faults", {}).get("bisections", 0)),
        "memory": _memory_summary(stream_gs.search_report),
    }


def leg_chunkloop(cache_dir=None, n_rows=484, n_candidates=48,
                  folds=2, max_iter=25, tasks_per_batch=8):
    """Device-resident chunk loop (ISSUE 16): the SAME LogReg grid run
    with ``chunk_loop="per_chunk"`` vs ``"scan"``, WARM walls only,
    recording the launch-count collapse — per-chunk pays one launch
    per chunk per group while scan rolls each compile group's whole
    chunk axis into ONE ``lax.scan`` launch (``launches_per_group``
    -> 1.0) — and asserting byte-identical ``cv_results_``."""
    import numpy as np
    from sklearn.datasets import load_digits
    from sklearn.linear_model import LogisticRegression

    import spark_sklearn_tpu as sst

    X, y = load_digits(return_X_y=True)
    X = (X[:n_rows] / 16.0).astype(np.float32)
    y = y[:n_rows]
    grid = {"C": np.logspace(-4, 3, n_candidates).tolist()}

    def timed(mode, heartbeat=False):
        def mk():
            # small task batches force several chunks per compile
            # group, so the per-chunk arm's launch count is the
            # boundary tax being measured, not an artifact of one
            # giant chunk.  Pinned geometry costs keep BOTH arms on
            # identical planned widths — the global cost model learns
            # from the first arm's launches, and a width change means
            # a different reduction shape, which would turn the
            # byte-identity assertion into a 1-ulp lottery.
            return sst.GridSearchCV(
                LogisticRegression(max_iter=max_iter), grid, cv=folds,
                refit=False, backend="tpu",
                config=sst.TpuConfig(
                    compilation_cache_dir=cache_dir, chunk_loop=mode,
                    heartbeat=heartbeat,
                    max_tasks_per_batch=tasks_per_batch,
                    geometry_overhead_s=0.01,
                    geometry_lane_cost_s=1e-3))
        mk().fit(X, y)                      # warm the programs
        t0 = time.perf_counter()
        gs = mk().fit(X, y)
        return gs, round(time.perf_counter() - t0, 3)

    pc, wall_pc = timed("per_chunk")
    sc, wall_sc = timed("scan")
    # heartbeat A/B (ISSUE 17): the same scanned grid with the
    # in-flight beacon on — the beacon-bearing program compiles
    # separately (its presence joins the cache key), the wall delta
    # and the hub's own measured host fraction are the overhead the
    # <2% contract bounds, and the beat cadence is the watchdog's
    # operating signal
    hb, wall_hb = timed("scan", heartbeat=True)
    hb_blk = hb.search_report.get("heartbeat", {})
    blk = sc.search_report["chunkloop"]
    n_groups = max(1, len(sc.search_report.get("per_group", {})))
    n_l_pc = int(pc.search_report.get("n_launches", 0))
    n_l_sc = int(sc.search_report.get("n_launches", 0))
    parity = all(
        np.array_equal(np.asarray(pc.cv_results_[k]),
                       np.asarray(sc.cv_results_[k]))
        for k in pc.cv_results_ if "time" not in k and k != "params")
    return {
        "shape": f"digits[{n_rows}], {n_candidates} C x {folds} "
                 f"folds, {tasks_per_batch} tasks/batch",
        "per_chunk_warm_wall_s": wall_pc,
        "scan_warm_wall_s": wall_sc,
        "wall_ratio_per_chunk_over_scan": round(
            wall_pc / wall_sc, 3) if wall_sc else 0.0,
        "n_groups": n_groups,
        "n_launches_per_chunk": n_l_pc,
        "n_launches_scan": n_l_sc,
        "per_chunk_launches_per_group": round(n_l_pc / n_groups, 2),
        "scan_launches_per_group": round(n_l_sc / n_groups, 2),
        "launch_collapse_ratio": round(
            n_l_pc / n_l_sc, 2) if n_l_sc else 0.0,
        "n_segments": blk["n_segments"],
        "n_chunks_scanned": blk["n_chunks_scanned"],
        "n_launches_saved": blk["n_launches_saved"],
        "scan_fallbacks": list(blk["fallbacks"]),
        "scan_cv_results_identical": bool(parity),
        "heartbeat_warm_wall_s": wall_hb,
        "hb_wall_delta_frac": round(
            (wall_hb - wall_sc) / wall_sc, 4) if wall_sc else 0.0,
        "hb_overhead_frac": hb_blk.get("overhead_frac", 0.0),
        "hb_beats": hb_blk.get("beats_total", 0),
        "hb_cadence_p50_s": hb_blk.get("cadence_p50_s", 0.0),
        "hb_cadence_p95_s": hb_blk.get("cadence_p95_s", 0.0),
        "memory": _memory_summary(sc.search_report),
    }


def leg_pipeline_prefix(cache_dir=None, n_rows=484, n_prefixes=4,
                        n_suffixes=24, folds=2, max_iter=25,
                        tasks_per_batch=16):
    """Shared-prefix search graphs (ISSUE 19): the SAME
    StandardScaler->PCA->LogReg grid — ``n_prefixes`` distinct PCA
    widths x ``n_suffixes`` C values — run atomic
    (``prefix_reuse=False``, every candidate recomputes its chain
    inline) vs shared (each DISTINCT prefix computed once per fold and
    fanned over the suffixes), WARM walls only, recording the prefix
    compute collapse (``prefix_saved``; the headline contract is
    candidates/launches >= 5x at 4x24) and asserting byte-identical
    ``cv_results_``."""
    import numpy as np
    from sklearn.datasets import load_digits
    from sklearn.decomposition import PCA
    from sklearn.linear_model import LogisticRegression
    from sklearn.pipeline import Pipeline
    from sklearn.preprocessing import StandardScaler

    import spark_sklearn_tpu as sst

    X, y = load_digits(return_X_y=True)
    X = (X[:n_rows] / 16.0).astype(np.float32)
    y = y[:n_rows]
    pipe = Pipeline([("sc", StandardScaler()),
                     ("pca", PCA(random_state=0)),
                     ("clf", LogisticRegression(max_iter=max_iter))])
    comps = np.linspace(8, min(48, X.shape[1]),
                        n_prefixes).astype(int).tolist()
    grid = {"pca__n_components": comps,
            "clf__C": np.logspace(-4, 3, n_suffixes).tolist()}

    def timed(prefix_reuse):
        def mk():
            # pinned geometry costs keep BOTH arms on identical
            # planned widths (the global cost model learns from the
            # first arm's launches; a width change is a different
            # reduction shape = a 1-ulp lottery on the byte-identity
            # assertion)
            return sst.GridSearchCV(
                pipe, grid, cv=folds, refit=False, backend="tpu",
                config=sst.TpuConfig(
                    compilation_cache_dir=cache_dir,
                    prefix_reuse=prefix_reuse,
                    max_tasks_per_batch=tasks_per_batch,
                    geometry_overhead_s=0.01,
                    geometry_lane_cost_s=1e-3))
        mk().fit(X, y)                      # warm the programs
        t0 = time.perf_counter()
        gs = mk().fit(X, y)
        return gs, round(time.perf_counter() - t0, 3)

    atomic, wall_atomic = timed(False)
    shared, wall_shared = timed(True)
    px = shared.search_report["prefix"]
    n_cand = int(px["n_candidates_total"])
    n_launch = int(px["n_prefix_launches"])
    n_avoid = n_launch + int(px["n_prefix_reused"]) \
        + int(px["n_prefix_resumed"])
    parity = all(
        np.array_equal(np.asarray(atomic.cv_results_[k]),
                       np.asarray(shared.cv_results_[k]))
        for k in atomic.cv_results_ if "time" not in k and k != "params")
    return {
        "shape": f"digits[{n_rows}], {len(comps)} pca widths x "
                 f"{n_suffixes} C x {folds} folds, "
                 f"{tasks_per_batch} tasks/batch",
        "atomic_warm_wall_s": wall_atomic,
        "shared_warm_wall_s": wall_shared,
        # the rehearsal gate's throughput figure (every breadth leg
        # must produce one): fits/sec of the shared warm arm
        "fits_per_sec": round(n_cand * folds / wall_shared, 2)
        if wall_shared else 0.0,
        "wall_ratio_atomic_over_shared": round(
            wall_atomic / wall_shared, 3) if wall_shared else 0.0,
        "n_candidates": n_cand,
        "n_prefixes_distinct": int(px["n_prefixes_distinct"]),
        "n_prefix_launches": n_launch,
        "n_prefix_reused": int(px["n_prefix_reused"]),
        "prefix_saved": int(px["recompute_saved"]),
        # the headline: prefix computations per candidate collapse
        # from 1.0 to distinct/candidates (>= 5x reduction at 4x24)
        "prefix_compute_reduction": round(
            n_cand / n_avoid, 2) if n_avoid else 0.0,
        "prefix_bytes_cached": int(px["bytes_cached"]),
        "prefix_wall_s": px["prefix_wall_s"],
        "prefix_fallbacks": list(px["fallbacks"]),
        "prefix_cv_results_identical": bool(parity),
        "memory": _memory_summary(shared.search_report),
    }


#: (detail key, leg fn, kwargs builder) for the breadth legs the TPU
#: child runs after the headline; each failure is contained per-leg.
_BREADTH_LEGS = [
    ("svc_mxu", leg_svc_mxu, {}),
    ("svc_digits", leg_svc_digits, {}),
    ("config3_rf_randomized", leg_config3_rf, {}),
    ("config4_gbr_grid", leg_config4_gbr, {}),
    ("config5_scaler_mlp", leg_config5_mlp, {}),
    ("keyed_1000models", leg_keyed, {}),
    ("serve_contended", leg_serve_contended, {}),
    ("halving_adaptive", leg_halving, {}),
    ("stream_sparse", leg_stream_sparse, {}),
    ("chunkloop_scan", leg_chunkloop, {}),
    ("pipeline_prefix", leg_pipeline_prefix, {}),
]

#: scaled-down per-leg kwargs for the BENCH_FORCE_BREADTH=1 rehearsal
#: (VERDICT r4 next #1): the EXACT child code path the chip-unwedge
#: window will execute — headline then every breadth leg in sequence,
#: shared persistent compile cache, superseding milestone emissions —
#: at CPU-feasible shapes, so the rare TPU window runs pre-rehearsed
#: code end-to-end and spends its wall on the chip, not on surprises.
_BREADTH_TOY_KWARGS = {
    "svc_mxu": dict(n=96, d=16, folds=2, max_iter=10,
                    C_values=(1.0,), gamma_values=(0.01,)),
    "svc_digits": dict(n_C=2, n_gamma=1, folds=2, n_rows=200),
    "config3_rf_randomized": dict(n=400, d=8, n_classes=3, n_iter=2,
                                  folds=2, est_lo=5, est_hi=8,
                                  depth_lo=2, depth_hi=4),
    "config4_gbr_grid": dict(n=300, d=4, folds=2,
                             learning_rates=(0.1,), n_estimators=(10,)),
    "config5_scaler_mlp": dict(hidden=8, max_iter=5, folds=2,
                               alphas=(1e-3,)),
    "keyed_1000models": dict(n_keys=8, rows=10, d=3),
    "serve_contended": dict(n_rows=96, n_candidates=16, folds=2,
                            max_iter=5, levels=(2,)),
    "halving_adaptive": dict(n_rows=242, n_candidates=48, folds=2,
                             max_iter=10),
    "stream_sparse": dict(n=400, d=64, n_alphas=3, folds=2,
                          budget_mib=0.25),
    "chunkloop_scan": dict(n_rows=242, n_candidates=24, folds=2,
                           max_iter=10),
    "pipeline_prefix": dict(n_rows=242, n_prefixes=4, n_suffixes=24,
                            folds=2, max_iter=10),
}


def _traced(leg_key, trace_dir, fn, **kwargs):
    """Run one bench leg with the span tracer recording and export its
    Chrome trace next to the other artifacts.  Returns (result,
    trace_path); tracing failures never fail the leg."""
    import time as _time

    from spark_sklearn_tpu.obs.export import export_chrome_trace
    from spark_sklearn_tpu.obs.trace import get_tracer

    tracer = get_tracer()
    was_on = tracer.enabled
    if not was_on:
        tracer.clear()
        tracer.enable()
    # an already-on tracer (SST_TRACE) keeps its cumulative buffer, so
    # each leg's artifact exports only the events it recorded itself
    t_leg0 = _time.perf_counter()
    try:
        result = fn(**kwargs)
    finally:
        path = os.path.join(trace_dir, f"trace_{leg_key}.json")
        try:
            export_chrome_trace(
                path, events=[e for e in tracer.events()
                              if e[2] >= t_leg0])
        except Exception as exc:  # noqa: BLE001 — observability only
            sys.stderr.write(f"trace export failed for {leg_key}: "
                             f"{exc!r}\n")
            path = None
        if not was_on:
            tracer.disable()
    return result, path


def run_child(platform):
    import jax
    if platform == "cpu":
        jax.config.update("jax_platforms", "cpu")

    real_platform = jax.devices()[0].platform
    on_tpu = real_platform != "cpu"

    # Full-size grid on the chip; 1-core CPU gets a scaled-down grid
    # (the batched solver is ~100x slower there — minutes, not hours).
    n_candidates = 1000 if on_tpu else int(
        os.environ.get("BENCH_CPU_CANDIDATES", "40"))

    import tempfile
    # the persistent compile cache lives where the engine's one resolver
    # puts it (JAX_COMPILATION_CACHE_DIR, else the fixed in-checkout
    # default) — never at a temporary name, which could not hit across
    # runs.  A directory that already holds entries makes the "cold"
    # wall exclude compilation; that is labelled, not hidden.
    from spark_sklearn_tpu.parallel.pipeline import (
        resolve_compile_cache_dir)
    cache_dir = resolve_compile_cache_dir()
    cache_reused = os.path.isdir(cache_dir) and bool(os.listdir(cache_dir))

    # per-leg trace artifacts: each leg's JSON payload names the
    # Perfetto-loadable Chrome trace the tracer exported for it
    trace_dir = os.environ.get("BENCH_TRACE_DIR")
    if trace_dir:
        os.makedirs(trace_dir, exist_ok=True)
    else:
        trace_dir = tempfile.mkdtemp(prefix="sst_traces_")

    (detail, fits_per_sec, vs_baseline), headline_trace = _traced(
        "headline", trace_dir, leg_headline,
        cache_dir=cache_dir, n_candidates=n_candidates,
        measure_bf16=on_tpu)
    if headline_trace:
        detail["trace_file"] = headline_trace
    if cache_reused:
        detail["compile_cache_reused"] = True  # cold wall excludes compile

    # the static-analysis gate's cost, recorded next to the numbers it
    # protects (cheap: pure-AST pass, no device work)
    try:
        detail["sstlint_gate"] = leg_sstlint()
    except (Exception, SystemExit) as exc:
        # gate-cost probe only — collect_modules raises SystemExit on
        # an unparseable module, which must not kill the bench payload
        detail["sstlint_gate_error"] = repr(exc)[:300]

    # the cross-round trend digest (tools/bench_trend.py) over the
    # BENCH_rNN.json history already in the repo root, so each payload
    # carries its own before/after comparison context
    try:
        from tools.bench_trend import trend as _bench_trend
        detail["bench_trend"] = _bench_trend(
            os.path.dirname(os.path.abspath(__file__)))
    except Exception as exc:  # noqa: BLE001 — bookkeeping only
        detail["bench_trend_error"] = repr(exc)[:300]

    label = "TPU" if on_tpu else "CPU-fallback"
    from spark_sklearn_tpu.obs.provenance import provenance_block
    payload = {
        "metric": f"GridSearchCV {n_candidates}x5 LogReg digits — "
                  f"fits/sec on {label} "
                  "(speedup vs ideal 8-exec Spark-CPU proxy)",
        "value": round(fits_per_sec, 2),
        "unit": "fits/sec",
        "vs_baseline": round(vs_baseline, 2),
        "platform": real_platform if on_tpu else "cpu-fallback",
        # the shared env-fingerprint stamp (obs/provenance.py) — the
        # same block the flight recorder and the run log record, so
        # artifacts from one box correlate by env_digest
        "provenance": provenance_block(),
        "detail": detail,
    }
    if not on_tpu:
        payload["note"] = (
            "CPU smoke fallback on a scaled-down grid: measures XLA:CPU "
            "launch overhead on a 1-core host, NOT TPU performance — "
            "vs_baseline on this platform is not a framework figure")
    # milestone 1: the headline number exists even if a later leg hangs
    _emit(payload)

    # cold/prewarmed/warm probe: a second cold PROCESS runs against the
    # program store the first populated and must record store hits on
    # every compile group (n_compiles == 0) — the zero-cold-start
    # contract on top of the persistent-compile-cache hits the old
    # two-process probe asserted
    try:
        detail["persistent_cache_probe"] = leg_cache_probe(cache_dir)
    except Exception as exc:  # noqa: BLE001 — probe only
        detail["persistent_cache_probe_error"] = repr(exc)[:300]
    _emit(payload)

    force_breadth = os.environ.get("BENCH_FORCE_BREADTH") == "1"
    if on_tpu or force_breadth:
        for key, fn, kwargs in _BREADTH_LEGS:
            if not on_tpu:
                # rehearsal mode: same sequence, CPU-feasible shapes
                kwargs = {**kwargs, **_BREADTH_TOY_KWARGS.get(key, {})}
            try:
                leg_detail, leg_trace = _traced(
                    key, trace_dir, fn, cache_dir=cache_dir, **kwargs)
                if leg_trace and isinstance(leg_detail, dict):
                    leg_detail["trace_file"] = leg_trace
                detail[key] = leg_detail
            except Exception as exc:  # noqa: BLE001 — breadth only
                detail[f"{key}_error"] = repr(exc)[:300]
            _emit(payload)  # superseding milestone after every leg

    if not on_tpu and not force_breadth:
        # the adaptive-search trajectory (ISSUE 9) must exist in every
        # payload, CPU fallback included — it is THE bench history for
        # the halving line of work.  Unlike the scaled-down headline
        # this runs the REAL bench grid (full digits, 96 candidates):
        # rung row-compaction makes the halving arm's compute
        # proportional to its resource, so the leg is CPU-affordable
        # at full shape (~2 min) and the recorded ratio is the
        # acceptance figure, not a toy proxy
        try:
            leg_detail, leg_trace = _traced(
                "halving_adaptive", trace_dir, leg_halving,
                cache_dir=cache_dir, n_rows=1797, n_candidates=96,
                folds=2, max_iter=50)
            if leg_trace and isinstance(leg_detail, dict):
                leg_detail["trace_file"] = leg_trace
            detail["halving_adaptive"] = leg_detail
        except Exception as exc:  # noqa: BLE001 — breadth only
            detail["halving_adaptive_error"] = repr(exc)[:300]
        _emit(payload)

        # the chunk-loop A/B (ISSUE 16) must exist in every payload
        # too: launches_per_group is the trend column that keeps the
        # scan path's launch collapse honest across rounds, and the
        # leg is CPU-affordable because both arms run WARM at a
        # moderate grid
        try:
            leg_detail, leg_trace = _traced(
                "chunkloop_scan", trace_dir, leg_chunkloop,
                cache_dir=cache_dir)
            if leg_trace and isinstance(leg_detail, dict):
                leg_detail["trace_file"] = leg_trace
            detail["chunkloop_scan"] = leg_detail
        except Exception as exc:  # noqa: BLE001 — breadth only
            detail["chunkloop_scan_error"] = repr(exc)[:300]
        _emit(payload)

        # the shared-prefix A/B (ISSUE 19) must exist in every payload
        # too: prefix_saved is the trend column that keeps the
        # O(distinct-prefixes) collapse honest across rounds, and both
        # arms run WARM at a moderate 4x24 pipeline grid
        try:
            leg_detail, leg_trace = _traced(
                "pipeline_prefix", trace_dir, leg_pipeline_prefix,
                cache_dir=cache_dir)
            if leg_trace and isinstance(leg_detail, dict):
                leg_detail["trace_file"] = leg_trace
            detail["pipeline_prefix"] = leg_detail
        except Exception as exc:  # noqa: BLE001 — breadth only
            detail["pipeline_prefix_error"] = repr(exc)[:300]
        _emit(payload)

    return 0


def main():
    if len(sys.argv) >= 3 and sys.argv[1] == "--child":
        return run_child(sys.argv[2])
    return orchestrate()


if __name__ == "__main__":
    sys.exit(main())
