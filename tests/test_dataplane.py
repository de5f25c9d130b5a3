"""Device data plane (parallel/dataplane.py): the session-scoped
broadcast cache.

Contracts under test:
  - fingerprint-keyed residency: equal content shares one upload, a
    second identical search transfers ZERO cacheable bytes (X/y, fold
    masks) while per-chunk dyn staging keeps flowing;
  - byte-budgeted LRU: entries evict oldest-first, the budget holds;
  - on-device mask tiling: fold masks tile via a cached compiled
    broadcast, never a per-group host np.tile + upload;
  - `pad_chunk` writes into one preallocated buffer, bit-identical to
    the old concatenate-then-repeat implementation (satellite pin);
  - `search_report["dataplane"]` renders the pinned schema block.
"""

import warnings

import numpy as np
import pytest


import spark_sklearn_tpu as sst
from spark_sklearn_tpu.parallel import dataplane as dp
from spark_sklearn_tpu.parallel.taskgrid import pad_chunk


def _non_time_results(gs):
    return {k: v for k, v in gs.cv_results_.items()
            if "time" not in k and k != "params"}


def _assert_exact_equal(ra, rb):
    assert set(ra) == set(rb)
    for k in ra:
        np.testing.assert_array_equal(
            np.asarray(ra[k]), np.asarray(rb[k]), err_msg=k)


def _fit(X, y, grid=None, **cfg_kw):
    from sklearn.linear_model import LogisticRegression
    grid = grid or {"C": [0.1, 1.0, 10.0]}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return sst.GridSearchCV(
            LogisticRegression(max_iter=10), grid, cv=2, refit=False,
            backend="tpu", config=sst.TpuConfig(**cfg_kw)).fit(X, y)


def _data(n=120, d=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    return X, (X[:, 0] > 0).astype(np.int64)


class TestDataPlaneUnit:
    def test_content_keying_and_hit_counting(self):
        plane = dp.DataPlane(byte_budget=1 << 20)
        a = np.arange(64, dtype=np.float32)
        b = np.arange(64, dtype=np.float32)       # equal content, new obj
        d1 = plane.put(a, None, label="a")
        d2 = plane.put(b, None, label="b")
        assert d1 is d2
        assert plane.hits == 1 and plane.misses == 1
        assert plane.bytes_uploaded == a.nbytes
        # different content is a distinct resident
        plane.put(np.arange(1, 65, dtype=np.float32), None)
        assert plane.misses == 2 and plane.n_entries == 2

    def test_sharding_aware_keys(self):
        from spark_sklearn_tpu.parallel.mesh import (
            build_mesh, replicated_sharding, task_sharding)
        mesh = build_mesh(sst.TpuConfig())
        plane = dp.DataPlane(byte_budget=1 << 20)
        a = np.ones((8, 4), np.float32)
        d_repl = plane.put(a, replicated_sharding(mesh))
        d_task = plane.put(a, task_sharding(mesh))
        assert d_repl is not d_task          # same bytes, new placement
        assert plane.misses == 2
        assert plane.put(a, replicated_sharding(mesh)) is d_repl

    def test_lru_eviction_respects_budget(self):
        one_kb = np.zeros(256, np.float32)   # 1024 bytes
        plane = dp.DataPlane(byte_budget=3 * one_kb.nbytes)
        arrays = [np.full(256, i, np.float32) for i in range(4)]
        for a in arrays[:3]:
            plane.put(a, None)
        plane.put(arrays[0], None)           # refresh 0 -> LRU is 1
        plane.put(arrays[3], None)           # evicts 1
        assert plane.evictions == 1
        assert plane.bytes_in_cache <= plane.byte_budget
        hits = plane.hits
        plane.put(arrays[1], None)           # 1 is gone: re-uploads
        assert plane.hits == hits and plane.misses == 5

    def test_oversized_entry_survives_alone(self):
        plane = dp.DataPlane(byte_budget=128)
        big = np.zeros(1024, np.float32)
        d1 = plane.put(big, None)
        assert plane.n_entries == 1          # kept despite the budget
        assert plane.put(big, None) is d1

    def test_tiled_masks_cached_per_width(self):
        plane = dp.DataPlane(byte_budget=1 << 22)
        base = np.arange(12, dtype=np.float32).reshape(2, 6)
        base_dev = plane.put(base, None)
        t4 = plane.tiled(base, base_dev, 4, None)
        np.testing.assert_array_equal(
            np.asarray(t4), np.tile(base, (4, 1)))
        tiled_bytes = plane.bytes_tiled
        assert tiled_bytes == base.nbytes * 4
        # revisiting the width is a pure cache hit: no new tile bytes
        assert plane.tiled(base, base_dev, 4, None) is t4
        assert plane.bytes_tiled == tiled_bytes
        # a new width materializes (and is itself cached)
        t2 = plane.tiled(base, base_dev, 2, None)
        np.testing.assert_array_equal(
            np.asarray(t2), np.tile(base, (2, 1)))

    def test_upload_counter_and_span_bytes(self, clean_tracer):
        tracer = clean_tracer
        tracer.enable()
        b0 = dp.bytes_uploaded()
        arr = np.ones(100, np.float32)
        dp.upload(arr, None, label="probe")
        assert dp.bytes_uploaded() - b0 == arr.nbytes
        spans = [e for e in tracer.events()
                 if e[1] == "dataplane.upload"
                 and e[6].get("label") == "probe"]
        assert spans and spans[0][6]["bytes"] == arr.nbytes


class TestPadChunkPinned:
    """Satellite pin: the single-buffer pad_chunk is bit-identical to
    the old concatenate-then-repeat implementation."""

    @staticmethod
    def _reference(arr, lo, hi, width, repeat=1):
        chunk = arr[lo:hi]
        if len(chunk) != width:
            chunk = np.concatenate(
                [chunk, np.repeat(chunk[-1:], width - len(chunk), axis=0)])
        if repeat > 1:
            chunk = np.repeat(chunk, repeat, axis=0)
        return chunk

    @pytest.mark.parametrize("shape", [(13,), (13, 3), (13, 2, 4)])
    @pytest.mark.parametrize("repeat", [1, 2, 5])
    @pytest.mark.parametrize("lo,hi,width", [
        (0, 13, 13), (0, 8, 8), (3, 9, 8), (10, 13, 8), (12, 13, 4)])
    def test_bit_identical(self, shape, repeat, lo, hi, width):
        rng = np.random.RandomState(0)
        arr = rng.randn(*shape).astype(np.float32)
        expected = self._reference(arr, lo, hi, width, repeat)
        np.testing.assert_array_equal(
            pad_chunk(arr, lo, hi, width, repeat), expected)


class TestDataPlaneSearchIntegration:
    def test_second_search_reuses_everything_cacheable(self):
        X, y = _data()
        grid = {"C": np.logspace(-2, 1, 6).tolist()}
        first = _fit(X, y, grid)
        second = _fit(X, y, grid)
        d2 = second.search_report["dataplane"]
        assert d2["enabled"]
        assert d2["hits"] > 0
        assert d2["misses"] == 0, d2
        assert d2["bytes_uploaded"] == 0, d2     # no X/y/mask re-upload
        assert d2["mask_tiling"] == "device"
        _assert_exact_equal(_non_time_results(first),
                            _non_time_results(second))

    def test_disabled_plane_matches_exactly(self):
        X, y = _data(seed=3)
        grid = {"C": np.logspace(-2, 1, 6).tolist()}
        on = _fit(X, y, grid)
        off = _fit(X, y, grid, dataplane_bytes=0)
        d_off = off.search_report["dataplane"]
        assert d_off["enabled"] is False
        assert d_off["mask_tiling"] in ("host", "n/a")
        _assert_exact_equal(_non_time_results(on),
                            _non_time_results(off))

    def test_report_block_schema_keys(self):
        from spark_sklearn_tpu.obs.metrics import DATAPLANE_BLOCK_SCHEMA
        X, y = _data(seed=5)
        gs = _fit(X, y)
        block = gs.search_report["dataplane"]
        assert set(block) == {d.name for d in DATAPLANE_BLOCK_SCHEMA}

    def test_pipeline_records_stage_bytes(self):
        X, y = _data(seed=7)
        gs = _fit(X, y, {"C": np.logspace(-2, 1, 6).tolist()})
        pl = gs.search_report["pipeline"]
        assert pl["stage_bytes_total"] > 0
        staged = [t for t in pl["launches"] if t["kind"] == "fit"]
        assert staged and staged[0]["stage_bytes"] > 0

    def test_mask_upload_at_most_once_per_width(self, clean_tracer):
        """Acceptance pin: a traced run shows fold masks transferred at
        most once per (group width) — never once per launch."""
        tracer = clean_tracer
        tracer.enable()
        X, y = _data(seed=11)
        gs = _fit(X, y, {"C": np.logspace(-3, 2, 40).tolist()})
        n_chunk_launches = gs.search_report["n_launches"]
        assert n_chunk_launches >= 2          # several launches ran...
        mask_uploads = [e for e in tracer.events()
                        if e[1] == "dataplane.upload"
                        and str(e[6].get("label", "")).startswith("mask.")]
        tiles = [e for e in tracer.events() if e[1] == "dataplane.tile"]
        # ...but the base masks moved host->device at most a handful of
        # times (fit/test mask buffers), and each width tiled on device
        # at most once
        assert len(mask_uploads) <= 4, [e[6] for e in mask_uploads]
        widths = [e[6].get("reps") for e in tiles]
        assert len(widths) == len(set(widths)), widths
