"""MLP classifier/regressor families — jit-compiled minibatch training.

Reference counterpart: sklearn's MLPClassifier running unchanged inside a
Spark task (BASELINE config #5 exercises Pipeline(StandardScaler + MLP)).
Here the whole training loop is one XLA program: `lax.scan` over epochs, an
inner `lax.scan` over minibatches, adam/sgd updates inline — and `vmap`
lifts it over hyperparameter candidates so the MXU sees (candidates x batch)
matmuls instead of Python-loop epochs.

Numeric conventions follow sklearn's MLP (_multilayer_perceptron.py):
Glorot-uniform init, softmax/logistic output, mean cross-entropy (or 0.5*MSE
for regression) plus alpha*0.5*||W||^2/batch_n regularisation, default
batch_size=min(200, n), and sklearn's stopping rules compiled into a
`lax.while_loop` over epochs: training-loss plateau (`tol` /
`n_iter_no_change`), validation-score early stopping with best-weight
restore (`early_stopping=True` holds out `validation_fraction` of the
train-fold rows via a PRNG-derived held-out mask — same semantics as
sklearn's train_test_split, not the same row indices), and the sgd
`invscaling` / `adaptive` learning-rate schedules.  Under `vmap` the
while_loop runs until every candidate lane has stopped.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from spark_sklearn_tpu.models.base import (
    Family, NotCompiledError, encode_labels, register_family)

EPS = 1e-8


def _activation(name):
    return {
        "relu": jax.nn.relu,
        "tanh": jnp.tanh,
        "logistic": jax.nn.sigmoid,
        "identity": lambda x: x,
    }[name]


def _init_params(key, layer_sizes, dtype):
    """Glorot-uniform like sklearn's _init_coef."""
    params = []
    keys = jax.random.split(key, len(layer_sizes) - 1)
    for k, (fan_in, fan_out) in zip(keys, zip(layer_sizes[:-1],
                                              layer_sizes[1:])):
        bound = jnp.sqrt(6.0 / (fan_in + fan_out)).astype(dtype)
        kw, kb = jax.random.split(k)
        W = jax.random.uniform(kw, (fan_in, fan_out), dtype,
                               -bound, bound)
        b = jax.random.uniform(kb, (fan_out,), dtype, -bound, bound)
        params.append({"W": W, "b": b})
    return params


def _forward(params, X, act):
    h = X
    for layer in params[:-1]:
        h = act(h @ layer["W"] + layer["b"])
    return h @ params[-1]["W"] + params[-1]["b"]


def _check_supported(static):
    solver = static.get("solver", "adam")
    if solver not in ("adam", "sgd"):
        raise NotCompiledError(
            f"solver={solver!r} is not compiled; use backend='host'")
    if static.get("learning_rate", "constant") not in (
            "constant", "invscaling", "adaptive"):
        raise NotCompiledError(
            f"learning_rate={static.get('learning_rate')!r} is not "
            "compiled; use backend='host'")


class MLPClassifierFamily(Family):
    name = "mlp_classifier"
    is_classifier = True
    dynamic_params = {"alpha": np.float32,
                      "learning_rate_init": np.float32}
    #: sklearn's MLP keeps the user's X dtype all the way to the proba
    #: output (one of the two classifiers on this sklearn that do —
    #: everything else upcasts to f64; see grid.py's log_loss clip)
    proba_dtype_rule = "input"

    @classmethod
    def prepare_data(cls, X, y, dtype=np.float32):
        classes, y_enc = encode_labels(y)
        data = {
            "X": np.ascontiguousarray(X, dtype=dtype),
            "y": y_enc,
            "y1h": np.eye(len(classes), dtype=dtype)[y_enc],
        }
        meta = {"n_classes": int(len(classes)), "classes": classes,
                "n_features": int(X.shape[1])}
        return data, meta

    @classmethod
    def _out_dim(cls, meta):
        return meta["n_classes"]

    @classmethod
    def _loss_terms(cls, logits, data_slice, w):
        logp = jax.nn.log_softmax(logits, axis=1)
        per = -jnp.sum(data_slice["y1h"] * logp, axis=1)
        return jnp.sum(w * per)

    @classmethod
    def fit(cls, dynamic, static, data, train_w, meta):
        _check_supported(static)
        # device arrays throughout: minibatch rows are gathered by TRACED
        # permutation indices, which numpy inputs (a direct family.fit
        # call outside the engine) cannot serve
        data = {k: jnp.asarray(v) for k, v in data.items()}
        train_w = jnp.asarray(train_w)
        X = data["X"]
        n, d = X.shape
        dtype = X.dtype
        out_dim = cls._out_dim(meta)
        hidden = static.get("hidden_layer_sizes", (100,))
        if isinstance(hidden, int):
            hidden = (hidden,)
        layer_sizes = (d, *[int(h) for h in hidden], out_dim)
        act = _activation(static.get("activation", "relu"))
        solver = static.get("solver", "adam")
        alpha = jnp.asarray(
            dynamic.get("alpha", static.get("alpha", 1e-4)), dtype)
        lr = jnp.asarray(
            dynamic.get("learning_rate_init",
                        static.get("learning_rate_init", 1e-3)), dtype)
        max_iter = int(static.get("max_iter", 200))
        batch_size = static.get("batch_size", "auto")
        if batch_size == "auto":
            batch_size = min(200, n)
        batch_size = int(min(batch_size, n))
        n_batches = (n + batch_size - 1) // batch_size
        n_pad = n_batches * batch_size
        seed = static.get("random_state")
        seed = 0 if seed is None else int(seed)
        momentum = float(static.get("momentum", 0.9))
        b1 = float(static.get("beta_1", 0.9))
        b2 = float(static.get("beta_2", 0.999))
        eps_adam = float(static.get("epsilon", 1e-8))

        key = jax.random.PRNGKey(seed)
        key, init_key = jax.random.split(key)
        params = _init_params(init_key, layer_sizes, dtype)

        # per-batch targets gathered by index; pad with index 0, weight 0
        y_all = {k: data[k] for k in ("y1h",) if k in data}
        if "y_target" in data:
            y_all["y_target"] = data["y_target"]

        def batch_loss(p, idx, w_idx, a):
            Xb = X[idx]
            slice_ = {k: v[idx] for k, v in y_all.items()}
            logits = _forward(p, Xb, act)
            # clamp at 1 so a minibatch with zero training-fold rows makes a
            # harmless small step instead of a 1/EPS-exploded penalty grad
            wsum = jnp.maximum(jnp.sum(w_idx), 1.0)
            data_loss = cls._loss_terms(logits, slice_, w_idx) / wsum
            l2 = sum(jnp.sum(layer["W"] ** 2) for layer in p)
            return data_loss + 0.5 * a * l2 / wsum

        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        if solver == "adam":
            opt_state = {"m": zeros, "v": zeros,
                         "t": jnp.asarray(0.0, dtype)}

            def update(p, g, st, lr_eff):
                t = st["t"] + 1.0
                m = jax.tree_util.tree_map(
                    lambda m_, g_: b1 * m_ + (1 - b1) * g_, st["m"], g)
                v = jax.tree_util.tree_map(
                    lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, st["v"], g)
                mhat = jax.tree_util.tree_map(
                    lambda m_: m_ / (1 - b1 ** t), m)
                vhat = jax.tree_util.tree_map(
                    lambda v_: v_ / (1 - b2 ** t), v)
                p_new = jax.tree_util.tree_map(
                    lambda p_, mh, vh: p_ - lr_eff * mh /
                    (jnp.sqrt(vh) + eps_adam), p, mhat, vhat)
                return p_new, {"m": m, "v": v, "t": t}
        else:  # sgd with momentum
            opt_state = {"vel": zeros}

            def update(p, g, st, lr_eff):
                vel = jax.tree_util.tree_map(
                    lambda v_, g_: momentum * v_ - lr_eff * g_, st["vel"], g)
                p_new = jax.tree_util.tree_map(
                    lambda p_, v_: p_ + v_, p, vel)
                return p_new, {"vel": vel}

        # ---- sklearn stopping semantics (while_loop over epochs) ---------
        tol = float(static.get("tol", 1e-4))
        n_iter_no_change = int(static.get("n_iter_no_change", 10))
        early_stopping = bool(static.get("early_stopping", False))
        lr_schedule = static.get("learning_rate", "constant")
        power_t = float(static.get("power_t", 0.5))
        val_frac = float(static.get("validation_fraction", 0.1))

        if early_stopping:
            # hold out ~validation_fraction of the TRAIN-FOLD rows with a
            # PRNG mask: same semantics as sklearn's train_test_split
            # (score a held-out slice each epoch, restore best weights),
            # independent of the fold mask so every task shares one split
            key, vkey = jax.random.split(key)
            val_sel = (jax.random.uniform(vkey, (n,)) < val_frac).astype(
                dtype)
            fit_w = train_w * (1.0 - val_sel)
            val_w = train_w * val_sel
        else:
            fit_w = train_w
            val_w = None

        # sklearn advances its invscaling clock by the number of rows the
        # net actually trains on per epoch — the train-fold subset, minus
        # the early-stopping validation hold-out — not the full dataset
        n_fit_rows = jnp.sum((fit_w > 0).astype(dtype))

        def epoch_lr(it):
            """sklearn's SGDOptimizer.iteration_ends: lr fixed within an
            epoch, rescaled from the count of samples seen (invscaling);
            adam ignores schedules like sklearn's AdamOptimizer."""
            if solver != "sgd" or lr_schedule != "invscaling":
                return lr
            # epoch 0 runs at lr_init (sklearn decays AFTER each epoch,
            # from the count of samples seen so far)
            t_seen = it.astype(dtype) * n_fit_rows
            return lr / (t_seen + 1.0) ** power_t

        def run_epoch(p, st, ek, lr_eff):
            perm = jax.random.permutation(ek, n)
            # pad with index 0 at ZERO weight (a modulo wrap would silently
            # double-count wrapped samples at full weight)
            idx_pad = jnp.concatenate(
                [perm, jnp.zeros((n_pad - n,), perm.dtype)])
            wmul = jnp.concatenate(
                [jnp.ones((n,), dtype), jnp.zeros((n_pad - n,), dtype)])
            batches = idx_pad.reshape(n_batches, batch_size)
            wmuls = wmul.reshape(n_batches, batch_size)

            def one_batch(c, inp):
                p_, st_, acc = c
                idx, wm = inp
                w_idx = fit_w[idx] * wm
                loss, g = jax.value_and_grad(batch_loss)(
                    p_, idx, w_idx, alpha)
                wsum = jnp.maximum(jnp.sum(w_idx), 1.0)
                p_, st_ = update(p_, g, st_, lr_eff)
                # sklearn accumulates batch_loss * batch_size / n_total
                return (p_, st_, acc + loss * wsum), None

            (p, st, acc), _ = jax.lax.scan(
                one_batch, (p, st, jnp.asarray(0.0, dtype)),
                (batches, wmuls))
            wtot = jnp.maximum(jnp.sum(fit_w), 1.0)
            return p, st, acc / wtot

        def val_score(p):
            out = _forward(p, X, act)
            wsum = jnp.maximum(jnp.sum(val_w), jnp.asarray(1e-12, dtype))
            if cls.is_classifier:
                pred = jnp.argmax(out, axis=1)
                return jnp.sum(val_w * (pred == data["y"])) / wsum
            yt = data["y_target"]
            err = jnp.sum((out - yt) ** 2, axis=1)
            resid = jnp.sum(val_w * err) / wsum
            ym = jnp.sum(val_w[:, None] * yt, axis=0) / wsum
            tot = jnp.sum(val_w * jnp.sum((yt - ym[None, :]) ** 2,
                                          axis=1)) / wsum
            return 1.0 - resid / jnp.maximum(tot,
                                             jnp.asarray(1e-12, dtype))

        big = jnp.asarray(np.finfo(np.float32).max, dtype)
        state = dict(
            p=params, opt=opt_state, key=key,
            it=jnp.asarray(0, jnp.int32),
            stop=jnp.asarray(False),
            # best validation score (early stopping) / best loss (plateau)
            best_score=-big, best_loss=big,
            no_improve=jnp.asarray(0, jnp.int32),
            lr_div=jnp.asarray(1.0, dtype),      # adaptive: lr /= 5 steps
            best_p=params,
        )

        def cond(s):
            return jnp.logical_and(s["it"] < max_iter,
                                   jnp.logical_not(s["stop"]))

        def body(s):
            key, ek = jax.random.split(s["key"])
            lr_eff = epoch_lr(s["it"]) / s["lr_div"]
            p, opt, loss = run_epoch(s["p"], s["opt"], ek, lr_eff)
            if early_stopping:
                score = val_score(p)
                improved_tol = score >= s["best_score"] + tol
                is_best = score > s["best_score"]
                best_score = jnp.where(is_best, score, s["best_score"])
                best_p = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(is_best, new, old),
                    p, s["best_p"])
                best_loss = s["best_loss"]
            else:
                improved_tol = loss <= s["best_loss"] - tol
                best_loss = jnp.minimum(loss, s["best_loss"])
                best_score = s["best_score"]
                best_p = s["best_p"]
            no_improve = jnp.where(improved_tol, 0, s["no_improve"] + 1)
            trigger = no_improve > n_iter_no_change
            if solver == "sgd" and lr_schedule == "adaptive":
                # sklearn SGDOptimizer.trigger_stopping: while the CURRENT
                # lr is above 1e-6, divide by 5 and keep going; only stop
                # when the current lr has already decayed to <= 1e-6 (one
                # more decay round than gating on lr/5)
                can_decay = lr_eff > 1e-6
                lr_div = jnp.where(jnp.logical_and(trigger, can_decay),
                                   s["lr_div"] * 5.0, s["lr_div"])
                stop = jnp.logical_and(trigger,
                                       jnp.logical_not(can_decay))
                no_improve = jnp.where(trigger, 0, no_improve)
            else:
                lr_div = s["lr_div"]
                stop = trigger
            return dict(p=p, opt=opt, key=key, it=s["it"] + 1, stop=stop,
                        best_score=best_score, best_loss=best_loss,
                        no_improve=no_improve, lr_div=lr_div, best_p=best_p)

        s = jax.lax.while_loop(cond, body, state)
        final_p = s["best_p"] if early_stopping else s["p"]
        return {"layers": final_p, "n_iter": s["it"]}

    @classmethod
    def _logits(cls, model, static, X, meta):
        act = _activation(static.get("activation", "relu"))
        return _forward(model["layers"], X, act)

    @classmethod
    def decision(cls, model, static, X, meta):
        Z = cls._logits(model, static, X, meta)
        if meta.get("n_classes") == 2:
            # scorer contract: binary decision is a 1-D margin
            return Z[:, 1] - Z[:, 0]
        return Z

    @classmethod
    def predict(cls, model, static, X, meta):
        return jnp.argmax(cls._logits(model, static, X, meta),
                          axis=1).astype(jnp.int32)

    @classmethod
    def predict_proba(cls, model, static, X, meta):
        return jax.nn.softmax(cls._logits(model, static, X, meta), axis=1)

    @classmethod
    def sklearn_attrs(cls, model, static, meta):
        layers = model["layers"]
        attrs = {
            "coefs_": [np.asarray(l["W"]) for l in layers],
            "intercepts_": [np.asarray(l["b"]) for l in layers],
            "classes_": meta.get("classes"),
            "n_features_in_": meta["n_features"],
            "n_layers_": len(layers) + 1,
        }
        if "n_iter" in model:
            attrs["n_iter_"] = int(model["n_iter"])
        return attrs


class MLPRegressorFamily(MLPClassifierFamily):
    name = "mlp_regressor"
    is_classifier = False

    @classmethod
    def build_fit_data(cls, Xg, yg, meta):
        yt = yg.astype(Xg.dtype)
        # the loss consumes "y_target" in (n, n_targets) layout; keyed
        # fleets carry a single y column -> (n, 1)
        return {"X": Xg, "y": yt, "y_target": yt[:, None]}

    @classmethod
    def prepare_data(cls, X, y, dtype=np.float32):
        y = np.asarray(y, dtype=dtype)
        data = {
            "X": np.ascontiguousarray(X, dtype=dtype),
            "y": y,
            "y_target": y.reshape(len(y), -1),
        }
        meta = {"n_features": int(X.shape[1]),
                "n_targets": int(data["y_target"].shape[1])}
        return data, meta

    @classmethod
    def _out_dim(cls, meta):
        return meta["n_targets"]

    @classmethod
    def _loss_terms(cls, preds, data_slice, w):
        se = jnp.sum((preds - data_slice["y_target"]) ** 2, axis=1)
        return 0.5 * jnp.sum(w * se)

    @classmethod
    def predict(cls, model, static, X, meta):
        out = cls._logits(model, static, X, meta)
        return out[:, 0] if meta["n_targets"] == 1 else out

    @classmethod
    def sklearn_attrs(cls, model, static, meta):
        attrs = MLPClassifierFamily.sklearn_attrs.__func__(
            cls, model, static, meta)
        attrs.pop("classes_", None)
        return attrs


register_family(
    MLPClassifierFamily,
    "sklearn.neural_network._multilayer_perceptron.MLPClassifier",
    "sklearn.neural_network.MLPClassifier",
)
register_family(
    MLPRegressorFamily,
    "sklearn.neural_network._multilayer_perceptron.MLPRegressor",
    "sklearn.neural_network.MLPRegressor",
)
