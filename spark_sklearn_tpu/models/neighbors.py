"""Compiled k-nearest-neighbors families — a TPU-first redesign.

Reference behavior: KNeighborsClassifier/Regressor run as arbitrary
sklearn estimators inside Spark tasks (reference: grid_search.py ->
sklearn _fit_and_score), so every (candidate, fold) task recomputes the
FULL pairwise-distance problem from scratch on a CPU executor.

The TPU-first shape inverts that cost model: a launch runs ONE neighbour
search for all of its candidates and folds, and a (candidate, fold) task
then reads its answer off the search's cumulative votes.

  - **Row blocks.**  The rows go through the search R at a time
    (`lax.fori_loop`): a block's squared distances to every row, one
    `(R, d) @ (d, n)` product (`ops/pairwise.py`, float32-accurate:
    `PRECISION`), never an `(n, n)` matrix.  R is the largest block the
    memory ledger's budget leaves beside the launch's other arrays
    (`launch_tiles`, priced by `launch_workspace`); where no budget
    applies, a block's distances hold at most 1 GiB.
  - **One selection a row.**  Where every row is left out of exactly one
    fold's training rows (K-fold splits: `meta["held_out_once"]`) and the
    search scores no training rows, a row's test scores all come from the
    fold that tests it: the columns of its own fold are masked out and ONE
    `lax.top_k` picks the `max_k` nearest of the rest (the grid's largest
    n_neighbors; equal distances by column index).  Where the search also
    scores training rows (`return_train_score=True`, scikit-learn's
    meaning: a row against each fold that trains on it) or the folds do
    not partition the rows, a row is selected once a fold, among that
    fold's training rows.
  - **Votes.**  The block's neighbours' labels (the regressor: values and
    a count) are summed cumulatively over the neighbour rank, weighted
    both ways at once, uniform and 1/distance.  A task is then an INDEX:
    its candidate's k and weighting, its fold's table.  `weights` is a
    dynamic parameter (`dynamic_codes`), so a n_neighbors x weights grid
    is ONE compile group and one neighbour search.

A 30 x 2-candidate x 5-fold grid on 70 000 rows therefore costs one
product of 70 000 x 70 000 x 784 and 70 000 selections, where the
reference pays 300 brute-force problems of 14 000 x 56 000.

sklearn-semantics notes:
  - brute-force euclidean only (metric minkowski with p=2 / euclidean);
    other metrics raise -> Tier B host path.
  - weights in {"uniform", "distance"}; distance weights use 1/d with
    d clamped at 1e-12, so an exact-duplicate neighbor dominates the
    vote (sklearn's exact rule: zero-distance neighbors take the whole
    vote; the clamp reproduces it to float precision).
  - predict on rows that belong to the train fold sees the row itself
    as a zero-distance neighbor, exactly like sklearn's
    `KNeighborsClassifier.fit(Xtr).predict(Xtr)`.
  - ties in the vote: predict is the argmax of the vote table, so the
    smallest class index wins, as in sklearn.
  - KNN fit takes no sample_weight in sklearn -> weighted searches take
    the host tier (accepts_sample_weight = False).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from spark_sklearn_tpu.models.base import (
    Family, NotCompiledError, encode_labels, register_family)
from spark_sklearn_tpu.ops.pairwise import sq_dists

_EPS_DIST = 1e-12
#: the distances' product: float32-accurate (three bfloat16 passes on a
#: TPU, where the default for float32 is one and picks other neighbours;
#: XLA:CPU multiplies in float32 whatever is asked)
PRECISION = lax.Precision.HIGH
#: the weightings a task chooses between, by their index
WEIGHTS = ("uniform", "distance")
#: where no budget applies (a backend that reports no memory limit, or
#: the ledger off): a block's distances hold at most this many bytes
_UNBUDGETED_BLOCK_BYTES = 1 << 30
#: a block's rows are a multiple of the chip's sublane tile
_ROW_TILE = 8
#: `launch_tiles`' facts in a group's static
_BLOCK_ROWS = "__knn_block_rows__"
_EVERY_FOLD = "__knn_every_fold__"


def _check_metric(static):
    metric = static.get("metric", "minkowski")
    p = static.get("p", 2)
    if metric not in ("minkowski", "euclidean") or \
            (metric == "minkowski" and p not in (2, 2.0)):
        raise NotCompiledError(
            f"metric={metric!r}/p={p!r} is not compiled (brute euclidean "
            "only); use backend='host'")
    weights = static.get("weights", "uniform")
    if callable(weights):
        raise NotCompiledError("callable weights are not compiled; use "
                               "backend='host'")
    if weights not in WEIGHTS:
        raise NotCompiledError(
            f"weights={weights!r} is not compiled; use backend='host'")


def block_rows(n: int, max_rows: int) -> int:
    """Rows a block: the fewest blocks of at most `max_rows` rows (one
    row tile at least) that cover `n`, evened out over the blocks and
    rounded up to the row tile, at most `n`."""
    max_rows = max(_ROW_TILE, int(max_rows))
    blocks = -(-n // max_rows)
    rows = -(-n // blocks)
    return min(n, -(-rows // _ROW_TILE) * _ROW_TILE)


def _unbudgeted_rows(n: int) -> int:
    return block_rows(n, _UNBUDGETED_BLOCK_BYTES // (4 * n))


def tiles(static, meta, n: int):
    """(rows a block, blocks, whether a row is selected once a fold) of a
    launch over `n` rows: `launch_tiles`' facts, or without them the
    unbudgeted block and the folds as `meta` has them."""
    static = static or {}
    every = bool(static.get(_EVERY_FOLD,
                            not meta.get("held_out_once", False)))
    R = int(static.get(_BLOCK_ROWS) or _unbudgeted_rows(n))
    return R, -(-n // R), every


def select(d2, allowed, maxk):
    """The `maxk` nearest allowed columns of each row: (squared distances,
    column indices), +inf where a row has fewer allowed columns.  Equal
    distances come in column order (`lax.top_k` keeps the lower index
    first)."""
    neg, idx = lax.top_k(jnp.where(allowed, -d2, -jnp.inf), maxk)
    return -neg, idx


def cum_votes(d2k, idx, values):
    """Cumulative votes over the neighbour rank, both weightings:
    ``(2, ..., maxk, V)`` from neighbour lists ``(..., maxk)`` and the
    rows' ``(n, V)`` values."""
    valid = jnp.isfinite(d2k)
    w_dist = 1.0 / jnp.maximum(jnp.sqrt(jnp.where(valid, d2k, 1.0)),
                               _EPS_DIST)
    zero = jnp.zeros((), values.dtype)
    w = jnp.stack([jnp.where(valid, 1.0, zero).astype(values.dtype),
                   jnp.where(valid, w_dist, zero)])
    return jnp.cumsum(w[..., None] * values[idx][None], axis=-2)


def neighbour_votes(X, values, fold_w, maxk, R, every):
    """The launch's neighbour search: ``(2, S, n, maxk, V)`` cumulative
    votes, S = 1 (a row's own held-out fold) or the folds (`every`).
    `fold_w` (F, n) bool: the folds' training rows."""
    n = X.shape[0]
    sq = jnp.sum(X * X, axis=1)
    # the fold that holds a row out: the first that does not train on it
    held = jnp.argmax(~fold_w, axis=0)
    S = fold_w.shape[0] if every else 1

    def block(b, cum):
        # the last block ends at the last row: it may share rows with the
        # one before, which then get the same lists twice
        r0 = jnp.minimum(b * R, n - R)
        with jax.named_scope("sst.knn.distance"):
            d2 = jnp.maximum(sq_dists(
                lax.dynamic_slice_in_dim(X, r0, R), X,
                lax.dynamic_slice_in_dim(sq, r0, R), sq,
                precision=PRECISION), 0.0)
        with jax.named_scope("sst.knn.select"):
            if every:
                d2k, idx = lax.map(
                    lambda m: select(d2, m[None, :], maxk), fold_w)
            else:
                own = lax.dynamic_slice_in_dim(held, r0, R)
                d2k, idx = select(d2, held[None, :] != own[:, None], maxk)
                d2k, idx = d2k[None], idx[None]
        with jax.named_scope("sst.knn.vote"):
            return lax.dynamic_update_slice(
                cum, cum_votes(d2k, idx, values), (0, 0, r0, 0, 0))

    cum = jnp.zeros((2, S, n, maxk, values.shape[-1]), values.dtype)
    return lax.fori_loop(0, -(-n // R), block, cum)


class KNeighborsClassifierFamily(Family):
    name = "kneighbors_classifier"
    is_classifier = True
    dynamic_params = {"n_neighbors": np.int32, "weights": np.int32}
    dynamic_codes = {"weights": WEIGHTS}
    #: sklearn's vote tables are float64 regardless of X
    proba_dtype_rule = "float64"
    #: sklearn's KNeighbors fit has no sample_weight parameter
    accepts_sample_weight = False
    keyed_compatible = False

    @classmethod
    def extract_params(cls, estimator):
        return dict(estimator.get_params(deep=False))

    @classmethod
    def prepare_data(cls, X, y, dtype=np.float32):
        classes, y_enc = encode_labels(y)
        data = {"X": np.ascontiguousarray(X, dtype=dtype), "y": y_enc}
        meta = {"n_classes": int(len(classes)), "classes": classes,
                "n_features": int(X.shape[1]), "n_samples": int(len(X))}
        return data, meta

    @classmethod
    def observe_candidates(cls, candidates, base_params, meta):
        ks = [int(c.get("n_neighbors",
                        base_params.get("n_neighbors", 5)))
              for c in candidates] or [int(base_params.get("n_neighbors",
                                                           5))]
        meta["max_k"] = max(ks)
        # sklearn raises at kneighbors() when a fold's train count is
        # smaller than n_neighbors; the compiled vote table would
        # silently clip to k=n_train instead — refuse host-side so both
        # backends agree on such grids (ADVICE r3)
        mft = meta.get("min_fold_train_count")
        if mft is not None and meta["max_k"] > mft:
            raise ValueError(
                f"Expected n_neighbors <= n_samples_fit, but "
                f"n_neighbors = {meta['max_k']}, n_samples_fit = {mft} "
                f"(smallest CV train fold) — sklearn raises when "
                f"scoring such a fold")

    # -- what a launch holds and does -------------------------------------
    @classmethod
    def _channels(cls, meta):
        """What a neighbour votes: a class's one-hot row."""
        return int(meta["n_classes"])

    @classmethod
    def _values(cls, data, meta):
        return jax.nn.one_hot(data["y"], meta["n_classes"],
                              dtype=data["X"].dtype)

    @classmethod
    def _workspace(cls, n, meta, n_folds, R, every, itemsize=4):
        S = n_folds if every else 1
        maxk, V = int(meta.get("max_k", 5)), cls._channels(meta)
        # the loop's carried vote tables of both weightings, twice (the
        # carry and its update), and a block's votes and lists
        tables = 2 * 2 * S * n * maxk * V * itemsize
        lists = S * R * maxk * (2 * V + 2) * itemsize
        return {"fixed_bytes": R * n * itemsize + tables + lists
                + 2 * n * itemsize,
                # a task's vote table read out, all folds
                "per_candidate_bytes": n_folds * n * V * itemsize}

    @classmethod
    def launch_workspace(cls, n_samples, meta, n_folds, itemsize=4, *,
                         static=None, row_sets=1):
        """What a launch holds besides its arguments, for the memory
        ledger: ONE copy of a row block's distances (the mask and the
        selection stream them: compiled for a v5e at the cell's size, the
        launch's scratch is the block's distances and two copies of the
        vote tables, tests/test_scopes_tpu_compile.py), the cumulative
        vote tables of both weightings, and a candidate's vote tables
        read out, all folds."""
        n = int(n_samples)
        R, _, every = tiles(static, meta, n)
        return cls._workspace(n, meta, n_folds, R, every, itemsize)

    @classmethod
    def launch_tiles(cls, static, meta, n_folds, n_candidates, *,
                     n_samples, budget_bytes, resident_bytes,
                     return_train):
        """The row block and the selections a row: the largest block
        whose distances and copies fit what the budget leaves beside the
        resident arrays, the tables and the group's `n_candidates` (and
        the engine's masks of theirs), else the unbudgeted block; a
        selection a fold where training rows are scored too or the folds
        do not partition the rows."""
        n = int(n_samples)
        every = bool(return_train) or not meta.get("held_out_once", False)
        if budget_bytes:
            ws = cls._workspace(n, meta, n_folds, 0, every)
            left = (budget_bytes - resident_bytes - ws["fixed_bytes"]
                    - n_candidates * (ws["per_candidate_bytes"]
                                      + 2 * n_folds * n * 4))
            rows = block_rows(n, left // (4 * n))
        else:
            rows = _unbudgeted_rows(n)
        return {_BLOCK_ROWS: rows, _EVERY_FOLD: every}

    @classmethod
    def launch_stats(cls, models, static, meta):
        """No iterative solver: the launch's blocks, so that the engine
        records its host facts beside them."""
        _, blocks, _ = tiles(static, meta, meta["n_samples"])
        return {"knn_blocks": jnp.asarray(blocks, jnp.int32)}

    @classmethod
    def launch_facts(cls, static, meta, n_candidates, n_folds):
        """Distances computed and rows selected by a launch, whatever its
        width: the neighbour search is the launch's, not a candidate's."""
        n = meta["n_samples"]
        R, blocks, every = tiles(static, meta, n)
        return {"knn_distance_entries": blocks * R * n,
                "knn_selections": blocks * R * (n_folds if every else 1),
                "knn_block_rows": R}

    # -- the launch ---------------------------------------------------------
    @classmethod
    def _task_tables(cls, dynamic, static, data, train_w, meta):
        """``(B, n, V)``: each task's row of the cumulative votes, at its
        candidate's k and weighting, from its fold's (or each row's own
        fold's) neighbour lists."""
        _check_metric(static)
        n_folds = int(static.get("__n_folds__", 0))
        if n_folds <= 0:
            raise ValueError("engine must pass __n_folds__ for KNN")
        X = data["X"]
        B, n = train_w.shape
        maxk = min(int(meta.get("max_k", static.get("n_neighbors", 5))), n)
        R, _, every = tiles(static, meta, n)
        # tasks are candidate-major: the first candidate's are the folds
        cum = neighbour_votes(X, cls._values(data, meta),
                              train_w[:n_folds] > 0, maxk, R, every)
        k_task = jnp.broadcast_to(jnp.asarray(
            dynamic.get("n_neighbors", static.get("n_neighbors", 5)),
            jnp.int32), (B,))
        w_task = jnp.broadcast_to(jnp.asarray(dynamic.get(
            "weights", WEIGHTS.index(static.get("weights", "uniform"))),
            jnp.int32), (B,))
        fold = (jnp.arange(B, dtype=jnp.int32) % n_folds if every
                else jnp.zeros((B,), jnp.int32))
        with jax.named_scope("sst.knn.vote"):
            return jax.vmap(lambda w, s, k: cum[w, s, :, k, :])(
                w_task, fold, jnp.clip(k_task - 1, 0, maxk - 1))

    @classmethod
    def fit_task_batched(cls, dynamic, static, data, train_w, meta):
        votes = cls._task_tables(dynamic, static, data, train_w, meta)
        return {"proba": votes / jnp.maximum(
            jnp.sum(votes, axis=-1, keepdims=True), _EPS_DIST)}

    # -- prediction from cached votes (search-internal) -------------------
    @classmethod
    def predict(cls, model, static, X, meta):
        return jnp.argmax(model["proba"], axis=-1).astype(jnp.int32)

    @classmethod
    def predict_proba(cls, model, static, X, meta):
        return model["proba"]

    @classmethod
    def decision(cls, model, static, X, meta):
        if meta["n_classes"] == 2:
            # ranking twin of sklearn's predict_proba[:, 1] for AUC
            return model["proba"][:, 1]
        return model["proba"]

    @classmethod
    def sklearn_attrs(cls, model, static, meta):
        return {"classes_": meta["classes"],
                "n_features_in_": meta["n_features"]}


class KNeighborsRegressorFamily(KNeighborsClassifierFamily):
    name = "kneighbors_regressor"
    is_classifier = False

    @classmethod
    def prepare_data(cls, X, y, dtype=np.float32):
        data = {"X": np.ascontiguousarray(X, dtype=dtype),
                "y": np.ascontiguousarray(y, dtype=dtype)}
        meta = {"n_features": int(X.shape[1]), "n_samples": int(len(X))}
        return data, meta

    @classmethod
    def _channels(cls, meta):
        """What a neighbour votes: its value, and 1 for the weights' sum."""
        return 2

    @classmethod
    def _values(cls, data, meta):
        y = data["y"]
        return jnp.stack([y, jnp.ones_like(y)], axis=1)

    @classmethod
    def fit_task_batched(cls, dynamic, static, data, train_w, meta):
        votes = cls._task_tables(dynamic, static, data, train_w, meta)
        return {"pred": votes[..., 0] / jnp.maximum(votes[..., 1],
                                                    _EPS_DIST)}

    @classmethod
    def predict(cls, model, static, X, meta):
        return model["pred"]

    @classmethod
    def decision(cls, model, static, X, meta):
        raise NotImplementedError("KNeighborsRegressor has no decision")

    @classmethod
    def predict_proba(cls, model, static, X, meta):
        raise NotImplementedError("KNeighborsRegressor has no proba")

    @classmethod
    def sklearn_attrs(cls, model, static, meta):
        return {"n_features_in_": meta["n_features"]}


register_family(
    KNeighborsClassifierFamily,
    "sklearn.neighbors._classification.KNeighborsClassifier",
    "sklearn.neighbors.KNeighborsClassifier",
)
register_family(
    KNeighborsRegressorFamily,
    "sklearn.neighbors._regression.KNeighborsRegressor",
    "sklearn.neighbors.KNeighborsRegressor",
)
