"""Device-memory ledger (ISSUE 10).

Contracts under test:
  - `search_report["memory"]` renders exactly the pinned
    MEMORY_BLOCK_SCHEMA keys; with the ledger disabled
    (`TpuConfig(memory_ledger=False)`) the block is ABSENT, the rest
    of the report and `cv_results_` are byte-identical, and the
    process-global ledger is never touched (exact no-op);
  - the footprint model prices per-chunk bytes from abstract shapes
    (task-batched tiled masks, per-candidate dyn params, score
    outputs) and `width_cap` turns the HBM budget into a shard-
    multiple chunk-width ceiling;
  - a small `hbm_budget_bytes` makes `plan_geometry` plan narrower
    widths (capped flag set), the search completes with ZERO OOM
    bisections, and scores stay bit-exact vs the unconstrained run
    (widths are pure geometry);
  - injected OOMs stamp modeled-vs-budget bytes onto the fault events,
    dump a flight bundle carrying the full ledger snapshot, and train
    the ledger's safety margin;
  - the telemetry snapshot / Prometheus exposition carry per-device
    memory series that agree with the searches' memory blocks;
  - tools: trace_summary digests the per-group `memory.footprint`
    instants and the ledger section of flight bundles; fleet_top
    prints the pressure line.
"""

import glob
import json
import os

import numpy as np
import pytest

import spark_sklearn_tpu as sst
from spark_sklearn_tpu.obs import memory as obs_memory
from spark_sklearn_tpu.obs.metrics import MEMORY_BLOCK_SCHEMA, schema_markdown
from spark_sklearn_tpu.obs.trace import get_tracer
from spark_sklearn_tpu.parallel import memledger
from spark_sklearn_tpu.parallel.taskgrid import plan_geometry

from sklearn.linear_model import LogisticRegression
from sklearn.naive_bayes import GaussianNB

rng = np.random.RandomState(0)
X = rng.randn(96, 6).astype(np.float32)
y = (X[:, 0] + 0.25 * rng.randn(96) > 0).astype(np.int64)
GRID = {"C": np.logspace(-2, 1, 24).tolist()}
#: wide enough to chunk into several fused launches, so "oom@4" lands
#: on a steady-state fused chunk on any device count
GRID40 = {"C": np.logspace(-2, 1, 40).tolist()}


def small_search(param_grid=GRID, **cfg_kw):
    cfg = sst.TpuConfig(**cfg_kw)
    return sst.GridSearchCV(LogisticRegression(max_iter=10), param_grid,
                            cv=2, refit=False, backend="tpu", config=cfg)


@pytest.fixture(autouse=True)
def clean_ledger():
    """Every test starts and ends with a fresh process-global ledger —
    the safety margin is trained by OOM tests and must not leak into
    the width-ceiling assertions of later tests."""
    memledger.get_ledger().reset()
    yield memledger.get_ledger()
    memledger.get_ledger().reset()


# ---------------------------------------------------------------------------
# Footprint model + width cap units
# ---------------------------------------------------------------------------

class TestFootprintModel:
    def test_task_batched_breakdown(self):
        dyn = {"C": np.asarray([0.1, 1.0, 10.0], np.float32)}
        fp = memledger.model_group_footprint(
            dyn, width=8, n_folds=2, task_batched=True, n_samples=100,
            mask_itemsize=4, n_scorers=1, return_train=False,
            dtype_itemsize=4)
        # dyn: f32 repeated per fold = 8 bytes/candidate
        assert fp["dyn_bytes"] == 8 * 8
        # tiled masks: 2 folds x 100 samples x 4 bytes per candidate
        assert fp["mask_bytes"] == 8 * 2 * 100 * 4
        # outputs: per fold, one f32 score cell + one health byte
        assert fp["out_bytes"] == 8 * 2 * (4 + 1)
        assert fp["chunk_bytes"] == \
            fp["dyn_bytes"] + fp["mask_bytes"] + fp["out_bytes"]
        assert fp["per_candidate_bytes"] * 8 == fp["chunk_bytes"]

    def test_nested_family_no_mask_tile(self):
        dyn = {"var_smoothing": np.asarray([1e-9, 1e-8], np.float64)}
        fp = memledger.model_group_footprint(
            dyn, width=4, n_folds=3, task_batched=False, n_samples=50,
            n_scorers=2, return_train=True)
        assert fp["mask_bytes"] == 0          # base masks are resident
        assert fp["dyn_bytes"] == 4 * 8       # f64, no per-fold repeat
        # 3 folds x (2 scorers x 2 (train+test) x 4B + 1 health byte)
        assert fp["out_bytes"] == 4 * 3 * (2 * 2 * 4 + 1)

    def test_all_static_group_models_pad_operand(self):
        fp = memledger.model_group_footprint(
            {}, width=16, n_folds=2, task_batched=False, n_samples=10,
            dtype_itemsize=4)
        assert fp["dyn_bytes"] == 16 * 4      # the `_pad` axis operand

    def test_width_cap_math(self):
        # 10_000 budget, 1_000 resident, 100 B/candidate -> 90 -> 88
        # at shard multiple 8
        assert memledger.width_cap(10_000, 1_000, 100, 8, 512) == 88
        # no budget -> no cap; zero slope -> no cap
        assert memledger.width_cap(0, 0, 100, 8, 512) is None
        assert memledger.width_cap(10_000, 0, 0, 8, 512) is None
        # never below the shard count, never above the task cap
        assert memledger.width_cap(100, 0, 1_000, 8, 512) == 8
        assert memledger.width_cap(10 ** 12, 0, 1, 8, 512) == 512
        # the margin scales BOTH resident and slope down
        assert memledger.width_cap(10_000, 1_000, 100, 8, 512,
                                   margin=2.0) == 40

    def test_observe_oom_trains_margin(self, clean_ledger):
        ledger = clean_ledger
        assert ledger.safety_margin == 1.0
        # model said 8_000 fits in 10_000 and it OOMed: margin covers
        # at least the implied underestimate
        m = ledger.observe_oom(8_000, 10_000)
        assert m == pytest.approx(1.25 * 10_000 / 8_000)
        # budget-less OOM: multiplicative nudge, bounded
        for _ in range(20):
            m = ledger.observe_oom(0, 0)
        assert m <= 8.0
        assert ledger.counters()["n_oom"] == 21


class TestPlanGeometryCaps:
    def test_auto_mode_caps_and_flags(self):
        geo = plan_geometry([100], [None], 2, 1, 512,
                            overhead_override=0.05,
                            lane_cost_override=1e-3,
                            width_caps=[16])
        assert geo.groups[0].width == 16 and geo.groups[0].capped
        free = plan_geometry([100], [None], 2, 1, 512,
                             overhead_override=0.05,
                             lane_cost_override=1e-3)
        assert free.groups[0].width > 16 and not free.groups[0].capped

    def test_fixed_and_sorted_modes_respect_cap(self):
        fixed = plan_geometry([100], [None], 2, 1, 512, mode="fixed",
                              width_caps=[32])
        assert fixed.groups[0].width == 32
        graded = plan_geometry([100], [64], 2, 1, 512, width_caps=[16])
        assert graded.groups[0].width == 16 and graded.groups[0].sorted

    def test_cap_normalizes_to_shard_multiple(self):
        geo = plan_geometry([100], [None], 2, 8, 512,
                            overhead_override=0.05,
                            lane_cost_override=1e-3,
                            width_caps=[21])
        assert geo.groups[0].width == 16       # 21 -> 16 at multiple 8

    def test_preferred_width_respects_cap(self):
        geo = plan_geometry([100], [None], 2, 1, 512,
                            cost_model=None, width_caps=[16],
                            preferred=[64])
        assert geo.groups[0].width <= 16

    def test_cap_joins_plan_cache_key(self):
        kw = dict(sizes=[48], sorted_caps=[None], n_folds=2,
                  n_task_shards=1, max_width=512,
                  overhead_override=0.05, lane_cost_override=1e-3)
        a = plan_geometry(reuse=True, **kw)
        b = plan_geometry(reuse=True, width_caps=[8], **kw)
        assert a.widths() != b.widths()


# ---------------------------------------------------------------------------
# search_report["memory"]: schema pin + ledger-off parity
# ---------------------------------------------------------------------------

class TestMemoryBlock:
    def test_block_keys_match_pinned_schema(self):
        gs = small_search().fit(X, y)
        mem = gs.search_report["memory"]
        assert list(mem) == [d.name for d in MEMORY_BLOCK_SCHEMA]
        assert mem["enabled"] is True
        assert mem["peak_modeled_bytes"] > mem["resident_bytes"] > 0
        g0 = mem["groups"][0]
        for k in ("group", "width", "capped", "resident_bytes",
                  "dyn_bytes", "mask_bytes", "out_bytes",
                  "per_candidate_bytes", "chunk_bytes"):
            assert k in g0, g0
        assert mem["n_samples"] >= 1

    def test_schema_markdown_documents_memory_block(self):
        md = schema_markdown()
        assert 'search_report["memory"]' in md
        for d in MEMORY_BLOCK_SCHEMA:
            assert f"`{d.name}`" in md

    def test_ledger_off_is_absent_and_byte_identical(self):
        on = small_search().fit(X, y)
        off = small_search(memory_ledger=False).fit(X, y)
        assert "memory" in on.search_report
        assert "memory" not in off.search_report
        # the rest of the report keeps the same shape, and scores are
        # byte-identical (the ledger never touches math)
        assert set(on.search_report) - set(off.search_report) == \
            {"memory"}
        for k in on.cv_results_:
            if "time" in k or k == "params":
                continue
            np.testing.assert_array_equal(
                np.asarray(on.cv_results_[k]),
                np.asarray(off.cv_results_[k]), err_msg=k)

    def test_ledger_off_exact_noop(self, clean_ledger):
        before = clean_ledger.counters()
        small_search(memory_ledger=False).fit(X, y)
        assert clean_ledger.counters() == before
        assert not clean_ledger.active
        assert clean_ledger.snapshot()["groups"] == []

    def test_halving_memory_block_namespaces_rungs(self):
        hs = sst.HalvingGridSearchCV(
            GaussianNB(),
            {"var_smoothing": np.logspace(-9, -5, 24).tolist()},
            cv=2, factor=3, random_state=7, backend="tpu")
        hs.fit(X, y)
        mem = hs.search_report["memory"]
        rungs = {str(g["group"]).split(":")[0]
                 for g in mem["groups"] if ":" in str(g["group"])}
        assert {"r0", "r1", "r2"} <= rungs, mem["groups"]


# ---------------------------------------------------------------------------
# The HBM width ceiling
# ---------------------------------------------------------------------------

class TestWidthCeiling:
    def test_low_budget_narrows_widths_exact_parity(self):
        base = small_search().fit(X, y)
        capped = small_search(hbm_budget_bytes=12_000).fit(X, y)
        wb = [g["width"] for g in
              base.search_report["geometry"]["groups"]]
        wc = [g["width"] for g in
              capped.search_report["geometry"]["groups"]]
        assert wc < wb
        assert any(g["capped"] for g in
                   capped.search_report["geometry"]["groups"])
        mem = capped.search_report["memory"]
        assert mem["budget_bytes"] == 12_000
        # every planned chunk's modeled footprint fits the budget
        assert all(g["chunk_bytes"] + g["resident_bytes"] <= 12_000
                   for g in mem["groups"])
        # the ceiling made bisection unnecessary, and scores are exact
        f = capped.search_report["faults"]
        assert f["bisections"] == 0 and \
            f["by_class"].get("oom", 0) == 0
        for k in base.cv_results_:
            if "time" in k or k == "params":
                continue
            np.testing.assert_array_equal(
                np.asarray(base.cv_results_[k]),
                np.asarray(capped.cv_results_[k]), err_msg=k)

    def test_budget_resolution_precedence(self, monkeypatch):
        monkeypatch.setenv("SST_HBM_BUDGET_BYTES", "5000")
        assert obs_memory.resolve_hbm_budget(None) == 5000
        assert obs_memory.resolve_hbm_budget(
            sst.TpuConfig(hbm_budget_bytes=7000)) == 7000
        assert obs_memory.resolve_hbm_budget(
            sst.TpuConfig(hbm_budget_bytes=0)) == 0
        monkeypatch.setenv("SST_HBM_BUDGET_BYTES", "junk")
        assert obs_memory.resolve_hbm_budget(None) == \
            obs_memory.resolve_hbm_budget(sst.TpuConfig())

    def test_detected_memory_fraction_default(self):
        stats = [{"measured": True, "bytes_limit": 10 ** 9,
                  "bytes_in_use": 0},
                 {"measured": True, "bytes_limit": 2 * 10 ** 9,
                  "bytes_in_use": 0}]
        assert obs_memory.detect_device_memory_bytes(stats) == 10 ** 9
        assert obs_memory.resolve_hbm_budget(
            sst.TpuConfig(), stats=stats) == int(
                10 ** 9 * obs_memory.DEFAULT_HBM_FRACTION)
        # unmeasured fleet (XLA:CPU): no ceiling by default
        assert obs_memory.resolve_hbm_budget(
            sst.TpuConfig(), stats=[{"measured": False,
                                     "bytes_limit": 0}]) == 0


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------

class TestOomForensics:
    def test_oom_events_and_bundle_carry_ledger(self, tmp_path,
                                                clean_ledger):
        base = small_search(GRID40).fit(X, y)
        gs = small_search(GRID40, fault_plan="oom@4",
                          retry_backoff_s=0.01,
                          flight_dir=str(tmp_path)).fit(X, y)
        np.testing.assert_array_equal(
            base.cv_results_["mean_test_score"],
            gs.cv_results_["mean_test_score"])
        ev = [e for e in gs.search_report["faults"]["events"]
              if e["class"] == "oom"]
        assert ev
        for e in ev:
            assert e["modeled_bytes"] > 0 and "budget_bytes" in e, e
        # the first OOM trained the safety margin once (dedup per
        # chunk: the bisect/host actions share the recover's training)
        assert gs.search_report["memory"]["safety_margin"] > 1.0
        assert clean_ledger.counters()["n_oom"] == 1
        bundles = glob.glob(str(tmp_path / "flight-oom-*.json"))
        assert bundles
        bundle = json.load(open(bundles[0]))
        assert bundle["memory"]["groups"], sorted(bundle)
        assert bundle["memory"]["modeled_peak_bytes"] > 0
        assert bundle["context"]["modeled_bytes"] > 0


# ---------------------------------------------------------------------------
# Telemetry agreement + exposition
# ---------------------------------------------------------------------------

class TestTelemetryMemory:
    def test_snapshot_agrees_with_search_block(self):
        from spark_sklearn_tpu.obs import telemetry as tel
        svc = tel.get_telemetry()
        cfg = sst.TpuConfig(telemetry_port=0, telemetry_interval_s=0.05)
        sess = sst.createLocalTpuSession("mem-tel-test", config=cfg)
        try:
            fut = sess.submit(small_search(telemetry_port=0), X, y)
            res = fut.result(timeout=300)
            sess.telemetry.sample_once()
            snap = sess.telemetry_snapshot()
            mem = snap["memory"]
            assert mem["modeled_peak_bytes"] >= \
                res.search_report["memory"]["peak_modeled_bytes"]
            assert mem["safety_margin"] == \
                res.search_report["memory"]["safety_margin"]
            assert mem["measured"] == \
                res.search_report["memory"]["measured"]
            assert "devices" in mem and "pressure_frac_max" in mem
            assert mem["pressure_window"], mem
        finally:
            sess.stop()
        assert not svc.enabled

    def test_prometheus_memory_families(self):
        snap = {
            "enabled": True, "window_s": 120.0, "n_samples": 3,
            "memory": {
                "measured": True, "watermark_bytes": 123,
                "modeled_peak_bytes": 456, "safety_margin": 1.5,
                "n_oom_observed": 2, "pressure_frac_max": 0.5,
                "devices": {"0": {"bytes_in_use": 100,
                                  "bytes_limit": 200,
                                  "pressure_frac": 0.5}}},
        }
        from spark_sklearn_tpu.obs.fleet import (
            METRIC_LINE_RE, prometheus_text)
        body = prometheus_text(snap)
        assert 'sst_memory_device_bytes_in_use{device="0"} 100' in body
        assert "sst_memory_modeled_peak_bytes 456" in body
        assert "sst_memory_safety_margin 1.5" in body
        assert "sst_memory_oom_observed_total 2" in body
        bad = [ln for ln in body.splitlines()
               if ln and not ln.startswith("#")
               and not METRIC_LINE_RE.match(ln)]
        assert not bad, bad[:5]


# ---------------------------------------------------------------------------
# Tools: trace digest + fleet_top
# ---------------------------------------------------------------------------

class TestTools:
    def test_trace_summary_memory_digest(self, tmp_path):
        from tools import trace_summary
        path = str(tmp_path / "trace.json")
        small_search(trace=path).fit(X, y)
        events = trace_summary.load_events(path)
        s = trace_summary.summarize(events)
        mem = s["memory"]
        assert mem["per_group_peak_modeled_bytes"], mem
        assert mem["n_samples"] >= 1
        text = trace_summary.format_summary(s)
        assert "memory: peak modeled footprint per compile group" \
            in text
        # no unknown-name warnings for the new span vocabulary
        assert not [n for n in s["unknown_names"]
                    if n.startswith("memory")]

    def test_trace_summary_digests_bundle_ledger(self, tmp_path):
        from tools import trace_summary
        gs = small_search(GRID40, fault_plan="oom@4",
                          retry_backoff_s=0.01,
                          flight_dir=str(tmp_path), trace=True)
        gs.fit(X, y)
        bundle = glob.glob(str(tmp_path / "flight-oom-*.json"))[0]
        assert trace_summary.load_bundle_memory(bundle)["groups"]
        rc = trace_summary.main([bundle])
        assert rc == 0

    def test_fleet_top_memory_line(self):
        from tools.fleet_top import format_snapshot
        snap = {
            "enabled": True, "window_s": 120.0, "n_samples": 1,
            "tenants": {"alpha": {"dispatches_total": 1,
                                  "tasks_total": 4,
                                  "residency_bytes": 2048}},
            "memory": {"measured": True, "modeled_peak_bytes": 10 ** 6,
                       "watermark_bytes": 5 * 10 ** 5,
                       "safety_margin": 1.25, "n_oom_observed": 1,
                       "devices": {"0": {"pressure_frac": 0.42}}},
        }
        text = format_snapshot(snap)
        assert "memory: modeled peak" in text
        assert "dev0=42.0%" in text
        assert "2.0 KiB" in text      # the tenant residency column

    def test_memory_sample_span_registered(self, tmp_path):
        from spark_sklearn_tpu.obs import spans
        assert spans.is_known_span("memory.sample")
        assert spans.is_known_span("memory.footprint")
        tracer = get_tracer()
        was = tracer.enabled
        if not was:
            tracer.enable()
        try:
            memledger.get_ledger().activate()
            memledger.note_launch_boundary()
            names = [e[1] for e in tracer.events()]
            assert "memory.sample" in names
        finally:
            memledger.get_ledger().deactivate()
            if not was:
                tracer.disable()


# --- Family.launch_workspace: one protocol -----------------------------------

def _registered_families():
    import spark_sklearn_tpu.models  # noqa: F401  (registers every family)
    from spark_sklearn_tpu.models.base import _FAMILIES_BY_CLASSNAME
    return sorted(set(_FAMILIES_BY_CLASSNAME.values()),
                  key=lambda f: f.__name__)


@pytest.mark.parametrize("family", _registered_families(),
                         ids=lambda f: f.__name__)
def test_every_family_takes_the_engines_workspace_call(family):
    """The engine asks once a compile group, with the group's `static`
    (a Pipeline adds `row_sets` for its final step): every family takes
    that call and answers with the ledger's two keys or with nothing."""
    meta = {"n_classes": 3, "n_features": 5, "n_targets": 1,
            "class_counts": (40, 40, 40)}
    ws = family.launch_workspace(120, meta, 3, 4, static={}, row_sets=3)
    assert set(ws) <= {"fixed_bytes", "per_candidate_bytes"}
    assert all(int(v) >= 0 for v in ws.values())
    if ws:
        footprint = memledger.model_group_footprint(
            {}, 4, 3, task_batched=False, n_samples=120, workspace=ws)
        assert footprint["fixed_bytes"] == ws.get("fixed_bytes", 0)
        assert footprint["chunk_bytes"] >= footprint["workspace_bytes"]
