"""MLP classifier/regressor families — jit-compiled minibatch training.

Reference counterpart: sklearn's MLPClassifier running unchanged inside a
Spark task (BASELINE config #5 exercises Pipeline(StandardScaler + MLP)).
Here the whole training loop is one XLA program: a `lax.while_loop` over
epochs, an inner loop over minibatches, adam/sgd updates inline — and
`vmap` lifts it over hyperparameter candidates and folds, so a launch
advances (candidates x folds) networks in lockstep.

Numeric conventions follow sklearn's MLP (_multilayer_perceptron.py,
_stochastic_optimizers.py): Glorot-uniform init, softmax output, mean
cross-entropy (or 0.5*MSE for regression) plus alpha*0.5*||W||^2/batch_n
regularisation, default batch_size=min(200, n), `AdamOptimizer`'s update
with the bias corrections folded into the step size, and sklearn's
stopping rules compiled into the epoch loop: training-loss plateau (`tol`
/ `n_iter_no_change`), validation-score early stopping with best-weight
restore (`early_stopping=True` holds out `validation_fraction` of the
train-fold rows via a PRNG-derived held-out mask — same semantics as
sklearn's train_test_split, not the same row indices), and the sgd
`invscaling` / `adaptive` learning-rate schedules.

**Minibatches are sklearn's**: a step trains on `batch_size` of the
fold's TRAINING rows and on no other row (the last step of an epoch on
what is left where the batch does not divide them), and each epoch
visits the training rows in a fresh random order.  Rows arrive as a
weight over all n rows (shapes are static: the fold's 0/1 mask times the
caller's `sample_weight`), so the training rows are the positive-weight
rows in data-set order, at positions 0 .. n_train - 1, and a step's loss,
regulariser and gradients are divided by its rows' sum of weights;
epoch e draws one uniform key for each of the n positions and visits
the first n_train positions in ascending order of their keys (stable).
All randomness comes from `random_state` by a written rule (`_init_params`,
`fit`), the same for every candidate: one gather of minibatch rows a
fold serves every candidate of a launch.

**Lockstep.**  The epoch loop runs until every lane of the launch's
candidate axis has stopped (`models.base.any_candidate`: the engine
names that axis); a stopped lane's steps are multiplied by 0.  The epoch
counter and the PRNG key therefore stay one value for all candidates and
only what differs by candidate (weights, moments, activations) is
batched by `vmap`.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from spark_sklearn_tpu.models.base import (
    Family, NotCompiledError, any_candidate, encode_labels, register_family)

EPS = 1e-8

#: copies of a lane's weights a launch holds for each array of optimiser
#: state (adam: weights and two moments, sgd: weights and velocity).  Read
#: off the launch compiled for a v5e at 60 lanes of (300,), (500, 300) and
#: (1000,): its scratch grows by 7.33 weight-sized arrays a lane and its
#: output is one more (the loop's state and the step's result side by
#: side, the initial weights broadcast to the lanes; the gradients are
#: never written out, the optimiser is fused into the products that make
#: them).  tests/test_scopes_tpu_compile.py holds the model to that
#: reading.
_COPIES_PER_STATE = 8.0 / 3.0


def _activation(name):
    return {
        "relu": jax.nn.relu,
        "tanh": jnp.tanh,
        "logistic": jax.nn.sigmoid,
        "identity": lambda x: x,
    }[name]


def _activation_slope(name, a):
    """d act(z) / dz written in a = act(z), as sklearn's DERIVATIVES."""
    if name == "relu":
        return (a > 0).astype(a.dtype)
    if name == "tanh":
        return 1.0 - a * a
    if name == "logistic":
        return a * (1.0 - a)
    return jnp.ones_like(a)


def _hidden_sizes(static):
    hidden = static.get("hidden_layer_sizes", (100,))
    if isinstance(hidden, (int, np.integer)):
        hidden = (hidden,)
    return tuple(int(h) for h in hidden)


def _init_params(key, layer_sizes, dtype, activation="relu"):
    """Glorot-uniform like sklearn's _init_coef: layer i takes key i of
    `split(key, n_layers)`, split once more into the weights' and the
    intercepts' key."""
    params = []
    factor = 2.0 if activation == "logistic" else 6.0
    keys = jax.random.split(key, len(layer_sizes) - 1)
    for k, (fan_in, fan_out) in zip(keys, zip(layer_sizes[:-1],
                                              layer_sizes[1:])):
        bound = jnp.sqrt(factor / (fan_in + fan_out)).astype(dtype)
        kw, kb = jax.random.split(k)
        W = jax.random.uniform(kw, (fan_in, fan_out), dtype,
                               -bound, bound)
        b = jax.random.uniform(kb, (fan_out,), dtype, -bound, bound)
        params.append({"W": W, "b": b})
    return params


def _activations(params, X, act):
    """X and every layer's activations; the last one is the output."""
    acts = [X]
    for layer in params[:-1]:
        acts.append(act(acts[-1] @ layer["W"] + layer["b"]))
    acts.append(acts[-1] @ params[-1]["W"] + params[-1]["b"])
    return acts


def _forward(params, X, act):
    return _activations(params, X, act)[-1]


#: rows a scoring forward pass takes at a time
_SCORE_ROWS = 4096


def _forward_by_blocks(params, X, act):
    """`_forward` over all rows, `_SCORE_ROWS` of them at a time.  A
    launch scores every lane on all n rows at once, and between two
    hidden layers the activations of all of them are an array of their
    own: 6.8 GB at 60 lanes of (500, 300) on 70 000 rows (the scoring
    launch compiled for a v5e; 0.8 GB by blocks), three times what
    training the same lanes holds."""
    n = X.shape[0]
    blocks = n // _SCORE_ROWS
    if len(params) < 3 or blocks < 2:
        return _forward(params, X, act)
    whole = blocks * _SCORE_ROWS
    out = jax.lax.map(lambda Xb: _forward(params, Xb, act),
                      X[:whole].reshape(blocks, _SCORE_ROWS, X.shape[1]))
    out = out.reshape(whole, out.shape[-1])
    if whole == n:
        return out
    return jnp.concatenate([out, _forward(params, X[whole:], act)])


def _predicted_class(logits):
    """argmax over the classes, behind an optimization barrier.  XLA:TPU
    (libtpu 0.0.34) fuses an argmax into the product that makes the
    logits and, for lanes of networks batched by `vmap`, gets it wrong:
    on the chip 8 of 12 lanes of (300,) nets with sound logits and 92 %
    accuracy read class 0 on every row, 10 % (PERF.md, PR 33; the same
    logits through the barrier, or taken to the host, read 92 %).  The
    barrier keeps the logits an array of their own."""
    return jnp.argmax(jax.lax.optimization_barrier(logits),
                      axis=-1).astype(jnp.int32)


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


def _training_rows(fit_w):
    """(rows, count): the rows of positive weight in data-set order, then
    the others, and how many the first are."""
    is_fit = fit_w > 0
    return (jnp.argsort(jnp.logical_not(is_fit), stable=True),
            jnp.sum(is_fit.astype(jnp.int32)))


def _batch_size(static, n):
    batch_size = static.get("batch_size", "auto")
    if batch_size == "auto":
        batch_size = min(200, n)
    return int(min(batch_size, n))


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
    def _targets(cls, data):
        return data["y1h"]

    @classmethod
    def _loss_and_delta(cls, out, target, w):
        """(sum over the batch's rows of w x the row's loss, its
        derivative in `out`): softmax cross-entropy."""
        logp = jax.nn.log_softmax(out, axis=1)
        loss = -jnp.sum(w * jnp.sum(target * logp, axis=1))
        return loss, (jnp.exp(logp) - target) * w[:, None]

    @classmethod
    def _layer_sizes(cls, static, meta):
        return (int(meta["n_features"]), *_hidden_sizes(static),
                int(cls._out_dim(meta)))

    @classmethod
    def fit(cls, dynamic, static, data, train_w, meta):
        _check_supported(static)
        # device arrays throughout: minibatch rows are gathered by TRACED
        # indices, which numpy inputs (a direct family.fit call outside
        # the engine) cannot serve
        data = {k: jnp.asarray(v) for k, v in data.items()}
        train_w = jnp.asarray(train_w)
        X = data["X"]
        target = cls._targets(data)
        n, d = X.shape
        dtype = X.dtype
        layer_sizes = (d, *_hidden_sizes(static), cls._out_dim(meta))
        act_name = static.get("activation", "relu")
        act = _activation(act_name)
        solver = static.get("solver", "adam")
        alpha = jnp.asarray(
            dynamic.get("alpha", static.get("alpha", 1e-4)), dtype)
        lr = jnp.asarray(
            dynamic.get("learning_rate_init",
                        static.get("learning_rate_init", 1e-3)), dtype)
        max_iter = int(static.get("max_iter", 200))
        batch_size = _batch_size(static, n)
        seed = static.get("random_state")
        seed = 0 if seed is None else int(seed)
        momentum = float(static.get("momentum", 0.9))
        b1 = float(static.get("beta_1", 0.9))
        b2 = float(static.get("beta_2", 0.999))
        eps_adam = float(static.get("epsilon", 1e-8))

        # from a static random_state: computed while the launch is traced
        # and constants of the program (no scope would name an operation)
        key = jax.random.PRNGKey(seed)
        key, init_key = jax.random.split(key)
        params = _init_params(init_key, layer_sizes, dtype, act_name)
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        if solver == "adam":
            opt_state = {"m": zeros, "v": zeros,
                         "t": jnp.asarray(0.0, dtype)}
        else:
            opt_state = {"vel": zeros}

        def backward(p, acts, delta, a, rows):
            """sklearn's _backprop: the gradients of the batch's mean
            loss plus 0.5 * a * ||W||^2 / rows, last layer first."""
            grads = [None] * len(p)
            for i in range(len(p) - 1, -1, -1):
                grads[i] = {
                    "W": (acts[i].T @ delta + a * p[i]["W"]) / rows,
                    "b": jnp.sum(delta, axis=0) / rows}
                if i:
                    delta = (delta @ p[i]["W"].T) * _activation_slope(
                        act_name, acts[i])
            return grads

        tmap = jax.tree_util.tree_map
        if solver == "adam":
            def update(p, g, st, lr_eff, live):
                # sklearn's AdamOptimizer: the bias corrections folded
                # into the step size, epsilon beside the raw sqrt(v)
                t = st["t"] + 1.0
                m = tmap(lambda m_, g_: b1 * m_ + (1 - b1) * g_,
                         st["m"], g)
                v = tmap(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_,
                         st["v"], g)
                lr_t = live * lr_eff * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
                p_new = tmap(
                    lambda p_, m_, v_: p_ - lr_t * m_ /
                    (jnp.sqrt(v_) + eps_adam), p, m, v)
                return p_new, {"m": m, "v": v, "t": t}
        else:  # sgd with momentum
            def update(p, g, st, lr_eff, live):
                vel = tmap(lambda v_, g_: momentum * v_ - lr_eff * g_,
                           st["vel"], g)
                p_new = tmap(lambda p_, v_: p_ + live * v_, p, vel)
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

        # the rows the net trains on (the train-fold subset, minus the
        # early-stopping hold-out) in data-set order, then the others:
        # a minibatch is cut from the first n_fit of them and holds no
        # other row
        fit_rows, n_fit = _training_rows(fit_w)
        slots = jnp.arange(n, dtype=jnp.int32)
        n_steps = (n_fit + batch_size - 1) // batch_size
        # sklearn advances its invscaling clock by the rows it trains on
        n_fit_rows = n_fit.astype(dtype)

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

        def run_epoch(p, st, ek, lr_eff, live):
            with jax.named_scope("sst.mlp.epoch"):
                # this epoch's order: position j < n_fit is visited by
                # ascending keys[j], ties by position
                keys = jnp.where(slots < n_fit,
                                 jax.random.uniform(ek, (n,)), 2.0)
                order = fit_rows[jnp.argsort(keys, stable=True)]

            def one_batch(i, c):
                p_, st_, acc, fullest = c
                with jax.named_scope("sst.mlp.gather"):
                    # the epoch's last step may have empty slots: they
                    # hold its last row again, at weight zero (no other
                    # row is ever gathered).  A filled slot's row keeps
                    # its sample weight, and the step divides by the
                    # batch's sum of them as sklearn's _backprop does
                    at = i * batch_size + jnp.arange(batch_size)
                    idx = order[jnp.clip(at, 0, jnp.maximum(n_fit - 1, 0))]
                    w = jnp.where(at < n_fit, fit_w[idx], 0).astype(dtype)
                    Xb, tb = X[idx], target[idx]
                    filled = jnp.sum((w > 0).astype(jnp.int32))
                    w_sum = jnp.sum(w)
                    rows = jnp.where(w_sum > 0, w_sum, 1.0)
                with jax.named_scope("sst.mlp.forward"):
                    acts = _activations(p_, Xb, act)
                    loss, delta = cls._loss_and_delta(acts[-1], tb, w)
                    l2 = sum(jnp.sum(layer["W"] ** 2) for layer in p_)
                    loss = (loss + 0.5 * alpha * l2) / rows
                with jax.named_scope("sst.mlp.backward"):
                    g = backward(p_, acts, delta, alpha, rows)
                with jax.named_scope("sst.mlp.update"):
                    p_, st_ = update(p_, g, st_, lr_eff, live)
                # sklearn accumulates batch_loss * batch rows / n_total
                return (p_, st_, acc + loss * filled.astype(dtype),
                        jnp.maximum(fullest, filled))

            p, st, acc, fullest = jax.lax.fori_loop(
                0, n_steps, one_batch,
                (p, st, jnp.asarray(0.0, dtype), jnp.asarray(0, jnp.int32)))
            return p, st, acc / jnp.maximum(n_fit_rows, 1.0), fullest

        def val_score(p):
            out = _forward(p, X, act)
            wsum = jnp.maximum(jnp.sum(val_w), jnp.asarray(1e-12, dtype))
            if cls.is_classifier:
                pred = _predicted_class(out)
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
            # the launch's epoch (one value for every candidate) and the
            # epochs this lane ran before it stopped
            epoch=jnp.asarray(0, jnp.int32),
            it=jnp.asarray(0, jnp.int32),
            stop=jnp.asarray(False),
            # best validation score (early stopping) / best loss (plateau)
            best_score=-big, best_loss=big,
            no_improve=jnp.asarray(0, jnp.int32),
            lr_div=jnp.asarray(1.0, dtype),      # adaptive: lr /= 5 steps
            steps=jnp.asarray(0, jnp.int32),
            fullest=jnp.asarray(0, jnp.int32),
        )
        if early_stopping:
            # the best epoch's weights: a whole copy, carried only where
            # it is read
            state["best_p"] = params

        def cond(s):
            return jnp.logical_and(
                s["epoch"] < max_iter,
                any_candidate(jnp.logical_not(s["stop"])))

        def body(s):
            live = jnp.logical_not(s["stop"])
            key, ek = jax.random.split(s["key"])
            lr_eff = epoch_lr(s["epoch"]) / s["lr_div"]
            p, opt, loss, fullest = run_epoch(
                s["p"], s["opt"], ek, lr_eff, live.astype(dtype))
            out = {}
            with jax.named_scope("sst.mlp.epoch"):
                if early_stopping:
                    score = val_score(p)
                    improved_tol = score >= s["best_score"] + tol
                    is_best = jnp.logical_and(live,
                                              score > s["best_score"])
                    best_score = jnp.where(is_best, score, s["best_score"])
                    out["best_p"] = tmap(
                        lambda new, old: jnp.where(is_best, new, old),
                        p, s["best_p"])
                    best_loss = s["best_loss"]
                else:
                    improved_tol = loss <= s["best_loss"] - tol
                    best_loss = jnp.minimum(loss, s["best_loss"])
                    best_score = s["best_score"]
                no_improve = jnp.where(improved_tol, 0, s["no_improve"] + 1)
                trigger = no_improve > n_iter_no_change
                if solver == "sgd" and lr_schedule == "adaptive":
                    # sklearn SGDOptimizer.trigger_stopping: while the
                    # CURRENT lr is above 1e-6, divide by 5 and keep
                    # going; only stop when the current lr has already
                    # decayed to <= 1e-6 (one more decay round than
                    # gating on lr/5)
                    can_decay = lr_eff > 1e-6
                    lr_div = jnp.where(jnp.logical_and(trigger, can_decay),
                                       s["lr_div"] * 5.0, s["lr_div"])
                    stop = jnp.logical_and(trigger,
                                           jnp.logical_not(can_decay))
                    no_improve = jnp.where(trigger, 0, no_improve)
                else:
                    lr_div = s["lr_div"]
                    stop = trigger
                # a stopped lane stays stopped while the launch's other
                # candidates finish; its counters keep their values
                def keep(new, old):
                    return jnp.where(live, new, old)

                out.update(
                    p=p, opt=opt, key=key, epoch=s["epoch"] + 1,
                    it=s["it"] + live.astype(jnp.int32),
                    stop=jnp.logical_or(s["stop"], stop),
                    best_score=best_score,
                    best_loss=keep(best_loss, s["best_loss"]),
                    no_improve=keep(no_improve, s["no_improve"]),
                    lr_div=keep(lr_div, s["lr_div"]),
                    steps=s["steps"] + n_steps,
                    fullest=jnp.maximum(s["fullest"], fullest))
            return out

        s = jax.lax.while_loop(cond, body, state)
        final_p = s["best_p"] if early_stopping else s["p"]
        return {"layers": final_p, "n_iter": s["it"],
                # lockstep minibatch steps the lane was carried through,
                # and the training rows of its fullest minibatch
                "n_steps": s["steps"], "batch_rows": s["fullest"]}

    @classmethod
    def launch_stats(cls, models, static, meta):
        """The default's epochs (maximum and sum over lanes), each
        task's own, the minibatch steps the launch executed and the
        training rows of its fullest minibatch."""
        stats = super().launch_stats(models, static, meta)
        stats["epochs"] = models["n_iter"].astype(jnp.int32).reshape(-1)
        stats["minibatch_steps"] = jnp.max(
            models["n_steps"]).astype(jnp.int32)
        stats["minibatch_rows"] = jnp.max(
            models["batch_rows"]).astype(jnp.int32)
        return stats

    @classmethod
    def _n_params(cls, static, meta):
        sizes = cls._layer_sizes(static, meta)
        return sum((i + 1) * o for i, o in zip(sizes[:-1], sizes[1:]))

    @classmethod
    def launch_facts(cls, static, meta, n_candidates, n_folds):
        """Weights and intercepts one lane trains."""
        return {"mlp_params": cls._n_params(static, meta)}

    @classmethod
    def launch_workspace(cls, n_samples, meta, n_folds, itemsize=4, *,
                         static, row_sets=1):
        """What a launch holds besides its arguments, for the memory
        ledger.  A lane (candidates x folds of them): `_COPIES_PER_STATE`
        copies of its weights for each array of optimiser state (with
        `early_stopping` the best epoch's weights too), and the step's
        activations and deltas, batch x every layer's units twice.
        Whatever the width: each fold's gathered minibatch and its
        targets, an epoch's order of rows, and of every matrix of rows
        the copy at half the width that the TPU's compiler keeps beside
        the loops, for products that read float32 rows in one bfloat16
        pass (read off the launch compiled for a v5e)."""
        sizes = cls._layer_sizes(static, meta)
        n_state = 3 if static.get("solver", "adam") == "adam" else 2
        copies = _COPIES_PER_STATE * n_state + (
            1 if static.get("early_stopping", False) else 0)
        batch = _batch_size(static, int(n_samples))
        lane = int(copies * cls._n_params(static, meta)
                   + 2 * batch * sum(sizes[1:])) * itemsize
        fixed = n_folds * (batch * (sizes[0] + sizes[-1]) * itemsize
                           + 3 * int(n_samples) * 4) + (
            row_sets * int(n_samples) * sizes[0] * (itemsize // 2))
        return {"fixed_bytes": fixed,
                "per_candidate_bytes": n_folds * lane}

    @classmethod
    def _logits(cls, model, static, X, meta):
        act = _activation(static.get("activation", "relu"))
        return _forward_by_blocks(model["layers"], X, act)

    @classmethod
    def decision(cls, model, static, X, meta):
        Z = cls._logits(model, static, X, meta)
        if meta.get("n_classes") == 2:
            # scorer contract: binary decision is a 1-D margin
            return Z[:, 1] - Z[:, 0]
        return Z

    @classmethod
    def predict(cls, model, static, X, meta):
        return _predicted_class(cls._logits(model, static, X, meta))

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
    def _targets(cls, data):
        return data["y_target"]

    @classmethod
    def _loss_and_delta(cls, out, target, w):
        """Half the squared error, sklearn's regression loss."""
        err = out - target
        return (0.5 * jnp.sum(w * jnp.sum(err * err, axis=1)),
                err * w[:, None])

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
