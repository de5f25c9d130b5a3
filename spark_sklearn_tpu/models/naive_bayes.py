"""Naive Bayes families — closed-form fits, the best case for the mesh.

Reference counterpart: sklearn's GaussianNB / MultinomialNB /
BernoulliNB running whole inside Spark tasks (reference: grid_search.py
-> sklearn _fit_and_score).  Every fit is a handful of weighted
reductions over X — no iterations at all — so a (candidate x fold) grid
compiles to a few wide matmuls with the fold masks as weights, and
parity with sklearn is at float tolerance, not accuracy level:

  - GaussianNB: per-class weighted mean/variance + the var_smoothing
    epsilon (sklearn _gaussian: epsilon_ = var_smoothing * max feature
    variance of the UNWEIGHTED train fold);
  - MultinomialNB: smoothed per-class feature count ratios
    (feature_log_prob = log(N_cf + a) - log(N_c + a*d));
  - BernoulliNB: binarized count ratios with the two-sided smoothing
    (p = (N_cf + a) / (N_c + 2a)) and the log(1-p) offset term;
  - ComplementNB: each class weighted by every OTHER class's counts
    (comp_count = feature_all + a - N_cf, negated log ratios, optional
    weight normalisation), prior only in the single-class case;
  - CategoricalNB: per-(feature, category) counts padded to the global
    max category count — one one-hot einsum to count, one to score
    (sklearn's ragged per-feature lists rebuilt on conversion).

The per-class sums are one (k, n) @ (n, d) matmul per task; XLA batches
tasks on the vmap axis.  sample_weight and class priors follow sklearn's
exact formulas (weighted counts everywhere except GaussianNB's epsilon).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from spark_sklearn_tpu.models.base import (
    Family, NotCompiledError, encode_labels, register_family)

_EPS = 1e-10


def _prep_classifier_data(X, y, dtype, x_override=None):
    """Shared prepare_data body: encoded labels + one-hot + meta.
    `x_override` supplies a pre-built device array for data["X"]
    (CategoricalNB's int codes) so no dead float copy of X is made."""
    classes, y_enc = encode_labels(y)
    k = len(classes)
    data = {"X": (np.ascontiguousarray(X, dtype=dtype)
                  if x_override is None else x_override),
            "y": y_enc,
            "y1h": np.eye(k, dtype=dtype)[y_enc]}
    meta = {"n_classes": int(k), "classes": classes,
            "n_features": int(X.shape[1])}
    return data, meta


def _prep_classifier_sparse(X, y, dtype):
    """Sparse twin of `_prep_classifier_data`: X is scipy CSR and stays
    a `SparseOperand` — labels/one-hot build exactly as on the dense
    path, X itself is never densified."""
    from spark_sklearn_tpu.sparse.csr import SparseOperand
    classes, y_enc = encode_labels(y)
    k = len(classes)
    op = SparseOperand.from_csr(X, dtype=dtype)
    data = {"X": op,
            "y": y_enc,
            "y1h": np.eye(k, dtype=dtype)[y_enc]}
    # the operand's signature tuple (truthy, hashable): flows through
    # freeze(meta) into ProgramStore keys and fusion keys, so a sparse
    # program can never alias a dense one with the same dense shape
    meta = {"n_classes": int(k), "classes": classes,
            "n_features": int(X.shape[1]), "sparse": op.signature()}
    return data, meta


def _class_sums(y1h, w, X=None):
    """Weighted per-class row sums: counts (k,), the (n, k) weighted
    one-hot used to build them, and, with X, per-class weighted feature
    sums (k, d) as ONE matmul."""
    wy = y1h * w[:, None]                       # (n, k)
    counts = jnp.sum(wy, axis=0)                # (k,)
    if X is None:
        return counts, wy, None
    return counts, wy, wy.T @ X                 # (k, d)


def _log_prior(counts, static, k, dtype):
    """sklearn _BaseDiscreteNB._update_class_log_prior."""
    class_prior = static.get("class_prior")
    if class_prior is not None:
        return jnp.log(jnp.asarray(class_prior, dtype))
    if static.get("fit_prior", True):
        return jnp.log(counts) - jnp.log(jnp.sum(counts))
    return jnp.full((k,), -np.log(k), dtype)


class GaussianNBFamily(Family):
    name = "gaussian_nb"
    is_classifier = True
    dynamic_params = {"var_smoothing": np.float32}
    # stays f32 deliberately: sklearn's GaussianNB preserves a float32
    # X end to end (f32 jll, f32 probas, log_loss clipped at f32 eps),
    # so the f32 engine mode IS the parity mode — an x64 override was
    # tried and made neg_log_loss diverge (f64 probas clip at 2.2e-16
    # where sklearn's f32 probas clip at 1.19e-7)
    proba_dtype_rule = "input"

    @classmethod
    def observe_candidates(cls, candidates, base_params, meta):
        """Host-side, once per search: sklearn's priors validation
        (GaussianNB._partial_fit) — a bad priors array must raise
        sklearn's clear messages, not an XLA broadcast error
        mid-trace."""
        k = meta.get("n_classes")
        seen = {id(None): None}
        for params in [base_params] + list(candidates):
            priors = params.get("priors")
            if priors is None or id(priors) in seen:
                continue
            seen[id(priors)] = priors
            p = np.asarray(priors, np.float64)
            if k is not None and len(p) != k:
                raise ValueError(
                    "Number of priors must match number of classes.")
            if not np.isclose(p.sum(), 1.0):
                raise ValueError("The sum of the priors should be 1.")
            if (p < 0).any():
                raise ValueError("Priors must be non-negative.")

    @classmethod
    def prepare_data(cls, X, y, dtype=np.float32):
        return _prep_classifier_data(X, y, dtype)

    @classmethod
    def fit(cls, dynamic, static, data, train_w, meta):
        X, y1h = data["X"], data["y1h"]
        vs = jnp.asarray(dynamic.get(
            "var_smoothing", static.get("var_smoothing", 1e-9)), X.dtype)
        priors = static.get("priors")
        # true two-pass variance (sklearn's _update_mean_variance is
        # np.average((X - mu)^2, weights=sw)): residuals are taken about
        # each sample's OWN class mean via a label gather, because ANY
        # one-pass E[x^2]-E[x]^2 form — even shifted by the grand mean —
        # cancels catastrophically in f32 once a class offset dwarfs the
        # within-class spread (measured: var off 8x RELATIVE on digits'
        # near-constant features, which log(var) turns into 0.007 score
        # drift)
        counts, wy, sums = _class_sums(y1h, train_w, X)      # (k,), (k, d)
        cnt = jnp.maximum(counts, _EPS)[:, None]
        theta = sums / cnt                                   # (k, d)
        r = X - theta[data["y"]]                             # (n, d)
        var = (wy.T @ (r * r)) / cnt
        # epsilon_ follows the UNWEIGHTED variance of the train fold
        # (sklearn _gaussian.py: np.var(X, axis=0).max() on the X passed
        # to fit, before sample weights), two-pass about the fold mean.
        # Known deviation: rows whose sample_weight is exactly 0 are
        # indistinguishable from out-of-fold rows here, so they drop out
        # of this variance where sklearn keeps them — an
        # O(var_smoothing) effect.
        ind = (train_w > 0).astype(X.dtype)
        n_ind = jnp.maximum(jnp.sum(ind), 1.0)
        mu0 = (ind @ X) / n_ind                              # (d,)
        r0 = X - mu0[None, :]
        fold_var = (ind @ (r0 * r0)) / n_ind
        eps = vs * jnp.max(fold_var)
        var = var + eps
        if priors is not None:
            prior = jnp.asarray(priors, X.dtype)
        else:
            prior = counts / jnp.maximum(jnp.sum(counts), _EPS)
        return {"theta": theta, "var": var,
                "log_prior": jnp.log(jnp.maximum(prior, 0.0))}

    @classmethod
    def _jll(cls, model, X):
        theta, var = model["theta"], model["var"]            # (k, d)
        ll = -0.5 * jnp.sum(jnp.log(2.0 * np.pi * var), axis=1)  # (k,)
        # sklearn's DIRECT form (_gaussian.py: -0.5*sum((X-theta)^2/var)),
        # not the matmul expansion: with var floored at epsilon the
        # per-feature terms reach ~1/var_smoothing, where the expanded
        # x^2/var - 2x*theta/var + theta^2/var cross terms round
        # differently from the oracle by O(10) in the jll (measured
        # 0.017 proba drift on digits).  XLA fuses this broadcast-reduce
        # without materialising the (n, k, d) intermediate.
        q = 0.5 * jnp.sum(
            (X[:, None, :] - theta[None, :, :]) ** 2 / var[None, :, :],
            axis=2)                                          # (n, k)
        return model["log_prior"][None, :] + ll[None, :] - q

    @classmethod
    def predict(cls, model, static, X, meta):
        return jnp.argmax(cls._jll(model, X), axis=1).astype(jnp.int32)

    @classmethod
    def predict_proba(cls, model, static, X, meta):
        return jax.nn.softmax(cls._jll(model, X), axis=1)

    @classmethod
    def decision(cls, model, static, X, meta):
        jll = cls._jll(model, X)
        if meta["n_classes"] == 2:
            return jll[:, 1] - jll[:, 0]
        return jll

    @classmethod
    def sklearn_attrs(cls, model, static, meta):
        return {"theta_": np.asarray(model["theta"]),
                "var_": np.asarray(model["var"]),
                "class_prior_": np.exp(np.asarray(model["log_prior"])),
                "classes_": meta["classes"],
                "n_features_in_": meta["n_features"]}


class MultinomialNBFamily(Family):
    name = "multinomial_nb"
    is_classifier = True
    dynamic_params = {"alpha": np.float32}
    # the fit is {counts, feature counts} -> closed form: the count sums
    # are one `wy.T @ X` (operator form, BCOO-legal) and additive over
    # row shards, so both out-of-core tiers apply
    supports_sparse = True
    supports_stream = True

    @classmethod
    def observe_candidates(cls, candidates, base_params, meta):
        """Host-side class_prior length check (sklearn
        _update_class_log_prior) — same rationale as GaussianNB's priors
        validation: sklearn's clear error, not an XLA broadcast error."""
        k = meta.get("n_classes")
        if k is None:
            return
        for params in [base_params] + list(candidates):
            cp = params.get("class_prior")
            if cp is not None and len(np.asarray(cp)) != k:
                raise ValueError(
                    "Number of priors must match number of classes.")

    #: sklearn's check_non_negative names the concrete class
    _sklearn_display = "MultinomialNB"

    @staticmethod
    def _check_finite(Xa):
        """sklearn's check_array contract: NaN (which would pass a
        min()<0 test — NaN comparisons are False) and infinity both
        raise BEFORE any launch, with sklearn's OWN per-case message
        (delegated, so the wording can never drift from the installed
        sklearn), instead of becoming masked failed fits."""
        if not np.issubdtype(Xa.dtype, np.floating):
            return
        from sklearn.utils import assert_all_finite
        assert_all_finite(Xa, input_name="X")

    @classmethod
    def prepare_data(cls, X, y, dtype=np.float32):
        Xa = np.asarray(X)
        cls._check_finite(Xa)
        if np.min(Xa) < 0:
            # sklearn's exact complaint; surfaces host-side before any
            # launch (the engine's designed fallback runs sklearn, which
            # raises the same for every candidate)
            raise ValueError(
                f"Negative values in data passed to "
                f"{cls._sklearn_display} (input X)")
        return _prep_classifier_data(X, y, dtype)

    @classmethod
    def prepare_data_sparse(cls, X, y, dtype=np.float32):
        # the sign/finiteness contract runs on the stored values only —
        # implicit zeros are non-negative and finite by construction
        Xd = np.asarray(X.data)
        cls._check_finite(Xd)
        if Xd.size and np.min(Xd) < 0:
            raise ValueError(
                f"Negative values in data passed to "
                f"{cls._sklearn_display} (input X)")
        return _prep_classifier_sparse(X, y, dtype)

    @classmethod
    def _alpha(cls, dynamic, static, dtype):
        a = jnp.asarray(dynamic.get("alpha", static.get("alpha", 1.0)),
                        dtype)
        if not static.get("force_alpha", True):
            a = jnp.maximum(a, 1e-10)   # sklearn's _check_alpha clamp
        return a

    @classmethod
    def _fit_X(cls, static, X):
        """The matrix the count sums run over (Bernoulli binarizes)."""
        return X

    @classmethod
    def _model_from_sums(cls, dynamic, static, counts, fc, meta, dtype):
        """Closed-form model from the sufficient statistics
        (class counts (k,), per-class feature sums (k, d)) — the shared
        tail of `fit` and `stream_fit_finalize`, so the streamed fit is
        the in-core fit by construction."""
        k = meta["n_classes"]
        a = cls._alpha(dynamic, static, dtype)
        smoothed = fc + a
        flp = jnp.log(smoothed) \
            - jnp.log(jnp.sum(smoothed, axis=1))[:, None]
        return {"feature_log_prob": flp,
                "class_log_prior": _log_prior(counts, static, k, dtype),
                "class_count": counts}

    @classmethod
    def fit(cls, dynamic, static, data, train_w, meta):
        X = cls._fit_X(static, data["X"])
        counts, _wy, fc = _class_sums(data["y1h"], train_w, X)
        return cls._model_from_sums(dynamic, static, counts, fc, meta,
                                    X.dtype)

    # --- streaming-fold protocol -----------------------------------------
    @classmethod
    def stream_fit_partial(cls, static, data, fit_w, meta):
        X = cls._fit_X(static, data["X"])
        y1h = data["y1h"]

        def one_fold(w):
            counts, _wy, fc = _class_sums(y1h, w, X)
            return {"count": counts, "fc": fc}

        return jax.vmap(one_fold)(fit_w)        # leaves: (F, ...) sums

    @classmethod
    def stream_fit_finalize(cls, dynamic, static, stats, meta):
        return cls._model_from_sums(dynamic, static, stats["count"],
                                    stats["fc"], meta,
                                    stats["fc"].dtype)

    @classmethod
    def _jll(cls, model, X):
        return X @ model["feature_log_prob"].T \
            + model["class_log_prior"][None, :]

    predict = classmethod(GaussianNBFamily.predict.__func__)
    predict_proba = classmethod(GaussianNBFamily.predict_proba.__func__)
    decision = classmethod(GaussianNBFamily.decision.__func__)

    @classmethod
    def sklearn_attrs(cls, model, static, meta):
        return {"feature_log_prob_": np.asarray(
                    model["feature_log_prob"]),
                "class_log_prior_": np.asarray(model["class_log_prior"]),
                "class_count_": np.asarray(model["class_count"]),
                "classes_": meta["classes"],
                "n_features_in_": meta["n_features"]}


class ComplementNBFamily(MultinomialNBFamily):
    """Complement NB (Rennie et al. 2003, sklearn ComplementNB): each
    class's weights come from the counts of every OTHER class —
    comp_count = feature_all + alpha - feature_count — so imbalanced
    text corpora don't drown minority classes.  The class prior only
    enters the degenerate single-class case, exactly like sklearn."""

    name = "complement_nb"
    _sklearn_display = "ComplementNB"

    @classmethod
    def _model_from_sums(cls, dynamic, static, counts, fc, meta, dtype):
        k = meta["n_classes"]
        a = cls._alpha(dynamic, static, dtype)
        comp = jnp.sum(fc, axis=0)[None, :] + a - fc          # (k, d)
        logged = jnp.log(comp / jnp.sum(comp, axis=1, keepdims=True))
        if static.get("norm", False):
            flp = logged / jnp.sum(logged, axis=1, keepdims=True)
        else:
            flp = -logged
        return {"feature_log_prob": flp,
                "class_log_prior": _log_prior(counts, static, k, dtype),
                "class_count": counts}

    @classmethod
    def _jll(cls, model, X):
        jll = X @ model["feature_log_prob"].T
        # sklearn adds the prior only in the single-class degenerate case
        if model["class_log_prior"].shape[0] == 1:
            jll = jll + model["class_log_prior"][None, :]
        return jll


class BernoulliNBFamily(MultinomialNBFamily):
    name = "bernoulli_nb"

    @classmethod
    def prepare_data(cls, X, y, dtype=np.float32):
        # negative X is fine here (binarize thresholds it), but the
        # finiteness contract still applies: NaN > threshold is False,
        # so without the guard a NaN X would silently binarize to 0
        # where sklearn raises
        cls._check_finite(np.asarray(X))
        return _prep_classifier_data(X, y, dtype)

    @classmethod
    def prepare_data_sparse(cls, X, y, dtype=np.float32):
        cls._check_finite(np.asarray(X.data))
        return _prep_classifier_sparse(X, y, dtype)

    @classmethod
    def observe_candidates(cls, candidates, base_params, meta):
        super().observe_candidates(candidates, base_params, meta)
        if not meta.get("sparse"):
            return
        # binarize < 0 turns every implicit zero into a 1 — a DENSE
        # matrix in BCOO clothing; refuse host-side rather than emit a
        # silently-wrong sparse program
        b0 = base_params.get("binarize", 0.0)
        for params in [base_params] + list(candidates):
            b = params.get("binarize", b0)
            if b is not None and float(b) < 0:
                raise ValueError(
                    "binarize < 0 densifies a sparse X (implicit zeros "
                    "binarize to 1); use data_mode='device'")

    @classmethod
    def _binarized(cls, static, X):
        b = static.get("binarize", 0.0)
        if b is None:
            return X
        from jax.experimental import sparse as jsparse
        if isinstance(X, jsparse.BCOO):
            # threshold the stored values in place; implicit zeros stay
            # zero (b >= 0 is enforced host-side on the sparse path)
            return jsparse.BCOO(
                ((X.data > b).astype(X.data.dtype), X.indices),
                shape=X.shape, indices_sorted=X.indices_sorted,
                unique_indices=X.unique_indices)
        return (X > b).astype(X.dtype)

    @classmethod
    def _fit_X(cls, static, X):
        return cls._binarized(static, X)

    @classmethod
    def _model_from_sums(cls, dynamic, static, counts, fc, meta, dtype):
        k = meta["n_classes"]
        a = cls._alpha(dynamic, static, dtype)
        # two-sided smoothing: p_cf = (N_cf + a) / (N_c + 2a)
        log_p = jnp.log(fc + a) - jnp.log(counts + 2.0 * a)[:, None]
        log_1mp = jnp.log(counts[:, None] - fc + a) \
            - jnp.log(counts + 2.0 * a)[:, None]
        return {"feature_log_prob": log_p, "log_neg_prob": log_1mp,
                "class_log_prior": _log_prior(counts, static, k, dtype),
                "class_count": counts}

    @classmethod
    def _jll(cls, model, X_raw):
        # caller passes raw X; the threshold lives in static, which _jll
        # doesn't receive — so the view entry points re-binarize below
        raise NotImplementedError

    @classmethod
    def _jll_static(cls, model, static, X):
        Xb = cls._binarized(static, X)
        flp, lnp = model["feature_log_prob"], model["log_neg_prob"]
        return Xb @ (flp - lnp).T \
            + jnp.sum(lnp, axis=1)[None, :] \
            + model["class_log_prior"][None, :]

    @classmethod
    def predict(cls, model, static, X, meta):
        return jnp.argmax(cls._jll_static(model, static, X),
                          axis=1).astype(jnp.int32)

    @classmethod
    def predict_proba(cls, model, static, X, meta):
        return jax.nn.softmax(cls._jll_static(model, static, X), axis=1)

    @classmethod
    def decision(cls, model, static, X, meta):
        jll = cls._jll_static(model, static, X)
        if meta["n_classes"] == 2:
            return jll[:, 1] - jll[:, 0]
        return jll


class CategoricalNBFamily(MultinomialNBFamily):
    """Categorical NB: per-(feature, category) counts.  sklearn keeps a
    ragged list of (k, n_categories_i) arrays; the compiled form pads to
    the global max category count — counts are ONE
    einsum('nk,ndc->kdc') over the one-hot codes, and the jll is ONE
    einsum('ndc,kdc->nk') contraction per task.

    Documented deviation: n_categories_ is resolved from the FULL X of
    the search (static shapes), where sklearn's per-fit resolution uses
    only the train fold — in CV that makes sklearn RAISE at score time
    when a test fold holds a category its train fold never saw; the
    compiled path behaves as if `min_categories` covered the full data,
    which is sklearn's own documented fix for that crash."""

    name = "categorical_nb"
    _sklearn_display = "CategoricalNB"
    # int codes + one-hot einsums: neither the BCOO operator forms nor
    # the additive-sums streaming protocol apply — undo the inherited
    # Multinomial capabilities
    supports_sparse = False
    supports_stream = False
    #: consumes int codes + search-resolved n_categories meta, which the
    #: keyed fleet's generic build_fit_data cannot synthesise (same
    #: opt-out as the binned tree families) — keyed CategoricalNB runs
    #: per-key sklearn on the host instead of silently mis-smoothing
    keyed_compatible = False

    @classmethod
    def prepare_data(cls, X, y, dtype=np.float32):
        Xa = np.asarray(X)
        if np.issubdtype(Xa.dtype, np.floating) and \
                not np.isfinite(Xa).all():
            # NaN passes a min()<0 test (NaN comparisons are False) and
            # astype(int32) would turn it into garbage codes
            raise ValueError("Input X contains NaN.")
        if np.min(Xa) < 0:
            raise ValueError(
                "Negative values in data passed to CategoricalNB "
                "(input X)")
        codes = np.ascontiguousarray(Xa, dtype=np.int32)
        data, meta = _prep_classifier_data(codes, y, dtype,
                                           x_override=codes)
        meta["n_categories"] = (codes.max(axis=0) + 1).astype(np.int64)
        return data, meta

    @classmethod
    def observe_candidates(cls, candidates, base_params, meta):
        """Resolve min_categories into the padded category counts
        (sklearn _validate_n_categories, host-side)."""
        super().observe_candidates(candidates, base_params, meta)
        mc = base_params.get("min_categories")
        if any(c.get("min_categories", mc) is not mc for c in candidates):
            raise NotCompiledError(
                "min_categories changes the compiled shapes; grid it "
                "with backend='host'")
        if mc is not None and "n_categories" in meta:
            mc_arr = np.asarray(mc)
            if not np.issubdtype(mc_arr.dtype, np.signedinteger):
                raise ValueError(
                    "'min_categories' should have integral type. Got "
                    f"{mc_arr.dtype} instead.")
            d = len(meta["n_categories"])
            # shape check BEFORE np.maximum: a (2,) array must get
            # sklearn's message, not a raw broadcast error, and a
            # broadcastable-but-wrong (1,) must not slip through
            if mc_arr.ndim > 0 and mc_arr.shape != (d,):
                raise ValueError(
                    f"'min_categories' should have shape ({d},) when "
                    f"an array-like is provided. Got {mc_arr.shape} "
                    f"instead.")
            meta["n_categories"] = np.maximum(
                meta["n_categories"], mc_arr).astype(np.int64)

    @classmethod
    def fit(cls, dynamic, static, data, train_w, meta):
        codes, y1h = data["X"], data["y1h"]
        k = meta["n_classes"]
        ncat = jnp.asarray(meta["n_categories"])             # (d,)
        C = int(np.max(meta["n_categories"]))
        a = cls._alpha(dynamic, static, y1h.dtype)
        wy = y1h * train_w[:, None]                          # (n, k)
        counts = jnp.sum(wy, axis=0)                         # (k,)
        oh = jax.nn.one_hot(codes, C, dtype=y1h.dtype)       # (n, d, C)
        cat = jnp.einsum("nk,ndc->kdc", wy, oh)              # (k, d, C)
        # per-feature denominator: total + alpha * n_categories_i
        # (padded columns beyond a feature's category count hold zero
        # counts and are never gathered — codes stay < n_categories_i)
        denom = jnp.sum(cat, axis=2) + a * ncat[None, :].astype(y1h.dtype)
        flp = jnp.log(cat + a) - jnp.log(denom)[:, :, None]
        return {"feature_log_prob": flp,                     # (k, d, C)
                "class_log_prior": _log_prior(counts, static, k,
                                              y1h.dtype),
                "class_count": counts}

    @classmethod
    def _jll(cls, model, X):
        flp = model["feature_log_prob"]                      # (k, d, C)
        oh = jax.nn.one_hot(X.astype(jnp.int32), flp.shape[2],
                            dtype=flp.dtype)                 # (n, d, C)
        return jnp.einsum("ndc,kdc->nk", oh, flp) \
            + model["class_log_prior"][None, :]

    @classmethod
    def check_predict_X(cls, X, meta):
        """Host-side predict-input guard (TpuModel calls this): sklearn
        raises IndexError for a category the model never allocated —
        one_hot would silently zero it instead."""
        ncat = np.asarray(meta["n_categories"])
        codes = np.asarray(X)
        bad = codes >= ncat[None, :]
        if bad.any():
            i, j = np.argwhere(bad)[0]
            raise IndexError(
                f"index {int(codes[i, j])} is out of bounds for feature "
                f"{int(j)} with {int(ncat[j])} categories")

    @classmethod
    def sklearn_attrs(cls, model, static, meta):
        flp = np.asarray(model["feature_log_prob"])
        ncat = np.asarray(meta["n_categories"])
        return {"feature_log_prob_": [flp[:, i, :ncat[i]]
                                      for i in range(flp.shape[1])],
                "class_log_prior_": np.asarray(model["class_log_prior"]),
                "class_count_": np.asarray(model["class_count"]),
                "n_categories_": ncat,
                "classes_": meta["classes"],
                "n_features_in_": meta["n_features"]}


register_family(
    CategoricalNBFamily,
    "sklearn.naive_bayes.CategoricalNB",
)
register_family(
    GaussianNBFamily,
    "sklearn.naive_bayes.GaussianNB",
)
register_family(
    MultinomialNBFamily,
    "sklearn.naive_bayes.MultinomialNB",
)
register_family(
    ComplementNBFamily,
    "sklearn.naive_bayes.ComplementNB",
)
register_family(
    BernoulliNBFamily,
    "sklearn.naive_bayes.BernoulliNB",
)
