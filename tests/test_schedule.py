"""The launch schedule's seams (search/schedule.py), on the host.

  - `GroupPlan` states the launch-layout rule once: which widths are
    whole runs of a family's layout, how a width is rounded to them, and
    which static (so which program and which price) a launch gets;
  - `plan_groups` plans compile groups without a device: a convergence-
    sorted group gets no layout, the memory ledger's cap under one run
    is the price of the launch without the layout, and a journalled
    geometry of another structure still refuses to resume;
  - a finished search lets go of every device buffer it made: with the
    cycle collector off, nothing it uploaded outlives `fit` but the data
    plane's own cache.
"""

import gc

import numpy as np
import pytest

import jax

import spark_sklearn_tpu as sst
from spark_sklearn_tpu.obs.metrics import search_registry
from spark_sklearn_tpu.parallel.taskgrid import (
    CompileGroup, GeometryMismatchError)
from spark_sklearn_tpu.search.schedule import GroupPlan, plan_groups

LAID_OUT = {"kernel": "rbf", "shared": True}
PLAIN = {"kernel": "rbf"}


def _plan(run, nc):
    group = CompileGroup({}, {"C": np.ones(nc)}, np.arange(nc), [{}] * nc)
    return GroupPlan(gi=0, group=group, nc=nc, static=LAID_OUT,
                     static_plain=PLAIN, laid_out=True, run=run)


@pytest.mark.parametrize(
    "run,shards,nc,width,limit,expected",
    [
        # already whole runs: it stays, and so do the layout's facts
        (5, 1, 20, 10, 20, 10),
        # rounded up to whole runs where the limit allows
        (5, 1, 15, 7, 12, 10),
        # ... to whole runs of shard stripes (lcm of 3 and 2)
        (3, 2, 12, 4, 8, 6),
        # rounding up would pass the limit: rounded down
        (5, 1, 15, 7, 8, 5),
        # a ceiling (no limit) is only ever rounded down
        (5, 1, 15, 12, None, 10),
        # under one run, either way: it stays, and launches without the
        # layout's facts
        (5, 1, 15, 3, 4, 3),
        (5, 2, 15, 6, 8, 6),
    ])
def test_group_plan_layout_rule(run, shards, nc, width, limit, expected):
    plan = _plan(run, nc)
    w = plan.whole_width(width, shards, limit)
    assert w == expected
    whole = plan.whole_runs(w)
    assert whole == (w % run == 0)
    # the program and the ledger's price both read the static here
    assert plan.static_for(whole) is (LAID_OUT if whole else PLAIN)
    # a launch cut at an offset inside a run, or with a seam inside one,
    # is no launch of whole runs
    assert plan.whole_runs(run, run) and not plan.whole_runs(1, run)
    assert not plan.whole_runs(0, run, run + 1)


class _Family:
    """A family with a convergence proxy and a launch layout of runs of
    five, whose launches of whole runs hold less a candidate."""
    name = "fake"
    min_sort_candidates = 4

    def __init__(self, proxy=None):
        self.proxy = proxy
        self.priced = []

    def convergence_proxy(self, dynamic_params, static):
        return self.proxy

    def launch_layout(self, dynamic_params, static, meta, n_folds):
        n = len(dynamic_params["C"])
        return np.arange(n)[::-1], {"shared": True}, 5

    def launch_workspace(self, n_samples, meta, n_folds, itemsize=4, *,
                         static=None, row_sets=1):
        self.priced.append(dict(static))
        per = 1_000 if static.get("shared") else 100_000
        return {"fixed_bytes": 0, "per_candidate_bytes": per}


class _Ledger:
    safety_margin = 1.0

    def __init__(self):
        self.groups = []

    def note_group(self, rec):
        self.groups.append(rec)


class _Journal:
    path = "journal"

    def __init__(self):
        self.meta = {}

    def get_meta(self, key):
        return self.meta.get(key)

    def put_meta(self, key, value):
        self.meta[key] = value

    def get(self, chunk_id):
        return None


def _groups(*sizes):
    out, start = [], 0
    for i, n in enumerate(sizes):
        out.append(CompileGroup(
            {"kernel": "rbf", "g": i},
            {"C": np.logspace(-2, 2, n).astype(np.float32)},
            np.arange(start, start + n), [{}] * n))
        start += n
    return out


def _plan_groups(groups, family, **kw):
    return plan_groups(
        groups, base_params={}, family=family, meta={}, config=sst.TpuConfig(),
        n_folds=3, n_task_shards=1, max_cand_per_batch=64,
        fit_masks=np.ones((3, 40), np.float32), n_scorers=1,
        return_train=False, dtype=np.float32,
        metrics=search_registry("tpu"), **kw)


def test_plan_groups_sorted_group_gets_no_layout():
    family = _Family(proxy=np.arange(20)[::-1].astype(np.float32))
    (plan,), geo = _plan_groups(_groups(20), family)
    assert plan.sorted_chunks and not plan.laid_out and plan.run == 1
    assert "shared" not in plan.static
    # sorted by the proxy, ascending, through the candidates' indices
    assert list(plan.group.candidate_indices) == list(range(20))[::-1]
    assert all(cid.endswith(":s") for _, _, cid, _ in plan.chunks)
    assert plan.n_live == len(plan.chunks) == geo.groups[0].n_chunks
    # without a proxy the same group is laid out in runs of five
    (laid,), _ = _plan_groups(_groups(20), _Family())
    assert laid.laid_out and laid.run == 5 and laid.static["shared"]
    assert laid.nc_batch % 5 == 0
    assert all(cid.endswith(":k") for _, _, cid, _ in laid.chunks)


def test_plan_groups_cap_under_one_run_prices_the_plain_launch():
    family, ledger = _Family(), _Ledger()
    # room for at most two laid-out candidates: under one run of five
    mem_ctx = {"budget_bytes": 2_500, "groups": []}
    (plan,), _ = _plan_groups(_groups(20), family, ledger=ledger,
                              mem_ctx=mem_ctx, resident_bytes=0)
    assert plan.cap is not None and plan.cap < plan.run
    # priced first with the layout's facts, then, under one run, as the
    # launch without them: the plain price leaves the narrowest width
    assert family.priced[0]["shared"] and "shared" not in family.priced[1]
    assert plan.cap == 1 and plan.nc_batch == 1
    # the ledger notes the group at its width, priced without the facts
    assert ledger.groups[0]["width"] == 1 == mem_ctx["groups"][0]["width"]
    assert "shared" not in family.priced[-1]


def test_plan_groups_journalled_geometry_mismatch_raises():
    journal = _Journal()
    _plan_groups(_groups(20, 10), _Family(), ckpt=journal)
    assert journal.get_meta("geometry_plan") is not None
    # the same journal resumed under another grid structure
    with pytest.raises(GeometryMismatchError, match="different launch"):
        _plan_groups(_groups(20, 15), _Family(), ckpt=journal)
    # ... and under the same one it replays the journalled geometry
    (p0, p1), geo = _plan_groups(_groups(20, 10), _Family(), ckpt=journal)
    assert geo.source == "journal"


def _digits():
    rng = np.random.default_rng(0)
    X = rng.standard_normal((120, 6)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.int64)
    return X, y


@pytest.mark.parametrize("path", [
    {"dataplane_bytes": 0},
    {"dataplane_bytes": 0, "fuse_fit_score": False},
    {"dataplane_bytes": 0, "chunk_loop": "scan"},
    {},
])
def test_search_releases_its_device_buffers(path):
    from sklearn.linear_model import LogisticRegression
    from spark_sklearn_tpu.parallel import dataplane as dp
    X, y = _digits()
    config = sst.TpuConfig(max_tasks_per_batch=8 * 3, **path)
    gc.collect()
    gc.disable()
    try:
        before = jax.live_arrays()
        gs = sst.GridSearchCV(
            LogisticRegression(max_iter=20),
            {"C": np.logspace(-3, 1, 19).tolist()}, cv=3, refit=False,
            backend="tpu", config=config).fit(X, y)
        assert gs.search_report["pipeline"]["n_launches"] >= 1
        plane = dp.plane_for(config)
        cached = set()
        if plane is not None:
            with plane._lock:
                for value, *_ in plane._entries.values():
                    cached.update(map(id, jax.tree_util.tree_leaves(value)))
        left = [a for a in jax.live_arrays()
                if id(a) not in cached and not any(a is b for b in before)]
        assert not left, [(a.shape, a.dtype) for a in left]
    finally:
        gc.enable()
