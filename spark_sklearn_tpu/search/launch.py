"""What one launch hands back: the seam between ``search/grid.py``'s launch
paths and the families.

Every path of ``_run_groups`` (the fused per-chunk launch, the unfused fit
launch of a group's first chunk, the scanned segment, the bisection
recursion, the cross-search ``FuseSpec``) returns, gathers, merges, slices
and finalizes the same :class:`LaunchResult`.  Its ``stats`` are what the
family's ``launch_stats`` hook reported (``models/base.py``); how one
combines and which ``search_report`` series it feeds is declared once, in
``obs.metrics.LAUNCH_STATS``, and read only here.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import numpy as np

from spark_sklearn_tpu.obs.metrics import LAUNCH_STATS
from spark_sklearn_tpu.parallel import mesh as mesh_lib

_COMBINE = {"max": np.maximum, "sum": np.add}


def _per_task(stat: str) -> bool:
    return LAUNCH_STATS[stat].combine == "per_candidate"


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LaunchResult:
    """A launch's scores, failed-fit flags and solver stats: device
    arrays as a program's (or a ``lax.scan`` step's) output, numpy arrays
    after :meth:`to_host`."""

    test: Dict[str, Any]      # scorer -> (candidates, folds)
    train: Dict[str, Any]     # the same, {} without return_train_score
    bad: Any                  # (candidates, folds) bool: a model leaf is NaN
    stats: Dict[str, Any]     # stat -> int32 scalar, or per-task vector

    def to_host(self, n_real=None, n_folds=1) -> "LaunchResult":
        """On the host as numpy arrays, cut to the first ``n_real``
        candidates (per-task stats to their ``n_real * n_folds`` tasks)."""
        host = jax.tree_util.tree_map(
            np.asarray, mesh_lib.device_get_tree(self))
        return host if n_real is None else host.slice(0, n_real, n_folds)

    def slice(self, off, n, n_folds) -> "LaunchResult":
        """Candidates ``[off, off + n)``: one member's view of a launch
        shared across searches, or the real rows of a padded one.  Scalar
        stats are the launch's own and stay whole."""
        rows = slice(off, off + n)
        tasks = slice(off * n_folds, (off + n) * n_folds)
        return LaunchResult(
            {s: v[rows] for s, v in self.test.items()},
            {s: v[rows] for s, v in self.train.items()},
            None if self.bad is None else self.bad[rows],
            {k: v[tasks] if _per_task(k) else v
             for k, v in self.stats.items()})

    def step(self, i) -> "LaunchResult":
        """Step ``i`` of a scanned segment's stacked result."""
        return jax.tree_util.tree_map(lambda a: a[i], self)

    @staticmethod
    def merge(a: "LaunchResult", b: "LaunchResult") -> "LaunchResult":
        """The two halves of a bisected range as one (host) result.  A
        half without stats (evaluated on the host, quarantined) is the
        identity of "max" and "sum"; its tasks read -1 in a per-task
        stat."""
        stats = {}
        for k in {**a.stats, **b.stats}:
            if _per_task(k):
                stats[k] = np.concatenate([
                    r.stats.get(k, np.full(r.bad.size, -1, np.int32))
                    for r in (a, b)])
            else:
                stats[k] = functools.reduce(
                    _COMBINE[LAUNCH_STATS[k].combine],
                    [r.stats[k] for r in (a, b) if k in r.stats])
        return LaunchResult(
            {s: np.concatenate([a.test[s], b.test[s]]) for s in a.test},
            {s: np.concatenate([a.train[s], b.train[s]]) for s in a.train},
            np.concatenate([a.bad, b.bad]), stats)

    @staticmethod
    def host_fill(test, train, n, n_folds) -> "LaunchResult":
        """``n`` candidates whose cells were not computed by a launch
        (host evaluation, quarantine to error_score): no fit failed on
        the device and no solver stat exists."""
        return LaunchResult(test, train, np.zeros((n, n_folds), bool), {})


# a launch program's output tree travels with the program through the
# persistent program store (parallel/programstore.py: jax.export)
jax.export.register_pytree_node_serialization(
    LaunchResult, serialized_name="spark_sklearn_tpu.LaunchResult",
    serialize_auxdata=lambda aux: b"", deserialize_auxdata=lambda b: ())


def record_stats(metrics, stats, idx, n_candidates, n_folds,
                 group_rec) -> None:
    """Append one launch's ``stats`` (the family's host facts merged in)
    to the ``search_report`` series ``LAUNCH_STATS`` names for them, and
    write the compile group's own facts on its ``group_rec``.
    ``idx``: the cv_results_ positions of the launch's real candidates."""
    for stat, d in LAUNCH_STATS.items():
        value = stats.get(stat, d.fill)
        if value is None:
            continue
        if d.group:
            group_rec[stat] = int(value)
        series = metrics.series(d.name)
        if d.combine != "per_candidate":
            series.append(int(value))
            continue
        if not series:
            series.extend([-1] * n_candidates)
        # tasks are candidate-major and a candidate's folds share its count
        for ci, v in zip(idx, np.asarray(value).reshape(-1)[::n_folds]):
            series[int(ci)] = int(v)
