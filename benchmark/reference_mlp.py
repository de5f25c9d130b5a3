"""The plain reference of the perceptron cells: what one (candidate, fold)
fit of ``Pipeline([StandardScaler(), MLPClassifier(solver="adam")])`` has to
answer, written from scikit-learn's published definitions
(``StandardScaler``; ``MLPClassifier._backprop``, ``_fit_stochastic``;
``AdamOptimizer._get_updates``; Kingma & Ba 2015; Glorot & Bengio 2010) and
from nothing of the program under test.

It imports nothing of ``spark_sklearn_tpu`` and takes nothing the program
made.  Straightforward ``jax.numpy``: float32 with every matrix product at
``highest`` precision, one fold at a time, the candidates of one hidden
shape side by side (they differ in ``alpha`` only), no masks over the whole
data set: a fold's training rows are cut out and standardised on the host.
The control of the comparison is this same code with ``dtype=bfloat16``
(the rows, the weights, both moments and the activations in bfloat16).

One fit, as scikit-learn states it:

- the scaler: each column minus its mean over the fold's training rows,
  over its population standard deviation there (a constant column is
  divided by 1);
- the network: ``hidden_layer_sizes`` relu layers and a softmax over the
  classes; a minibatch's loss is its mean cross-entropy plus
  ``0.5 * alpha * sum ||W||^2 / rows of the batch`` (intercepts are not
  penalised);
- the minibatches: each epoch visits every training row once, in a fresh
  random order, ``batch_size`` rows a step and what is left in the last;
- the optimiser: ``m = b1 m + (1 - b1) g``, ``v = b2 v + (1 - b2) g^2``,
  ``lr_t = lr sqrt(1 - b2^t) / (1 - b1^t)``,
  ``p -= lr_t m / (sqrt(v) + eps)``, t counting steps from 1;
- ``max_iter`` epochs (the stopping rules need more than ``max_iter`` = 8
  epochs without improvement to fire, ``n_iter_no_change`` = 10: the
  reference refuses a configuration in which they could).

What scikit-learn draws from a numpy ``RandomState`` no device program can
follow, so the configuration writes the rule down (``assumed``) and this
file follows the writing (:func:`initial_weights`, :func:`epoch_orders`):
with ``key = jax.random.PRNGKey(random_state)`` (jax's default threefry
generator) and ``split`` = ``jax.random.split``,

- ``key, init_key = split(key)``; layer i (0 = the first hidden layer)
  takes ``split(init_key, n_layers)[i]``, split once more into ``(kw,
  kb)``: ``W_i = uniform(kw, (fan_in, fan_out), -b, b)``,
  ``b_i = uniform(kb, (fan_out,), -b, b)``, ``b = sqrt(6 / (fan_in +
  fan_out))`` in float32;
- epoch e = 0, 1, ...: ``key, ek = split(key)``;
  ``u = uniform(ek, (n,))`` over ALL n rows of the data set; the fold's
  training rows, in data-set order, stand at positions 0 .. n_train - 1,
  and the epoch visits position j in ascending order of ``u[j]``, equal
  keys by position (a stable argsort of ``u[:n_train]``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PREFIX = "mlp__"
DEFAULTS = {"hidden_layer_sizes": (100,), "activation": "relu",
            "solver": "adam", "alpha": 1e-4, "batch_size": "auto",
            "learning_rate_init": 1e-3, "max_iter": 200, "shuffle": True,
            "random_state": None, "tol": 1e-4, "early_stopping": False,
            "beta_1": 0.9, "beta_2": 0.999, "epsilon": 1e-8,
            "n_iter_no_change": 10}


def settings_of(config, candidate):
    """``MLPClassifier``'s parameters for one candidate: scikit-learn's
    defaults, the configuration's, the candidate's ``mlp__`` keys."""
    out = dict(DEFAULTS)
    out.update(config["estimator"]["params"])
    out.update({k[len(PREFIX):]: v for k, v in candidate.items()
                if k.startswith(PREFIX)})
    hidden = out["hidden_layer_sizes"]
    out["hidden_layer_sizes"] = tuple(
        int(h) for h in (hidden if np.ndim(hidden) else [hidden]))
    if (out["solver"], out["activation"], out["early_stopping"]) != (
            "adam", "relu", False):
        raise ValueError("this reference knows adam on relu layers "
                         "without early stopping only")
    if out["max_iter"] > out["n_iter_no_change"]:
        raise ValueError("the stopping rules could fire within max_iter "
                         "epochs; this reference runs them all")
    return out


def standardise(X_train, X_test, dtype=jnp.float32):
    """scikit-learn's ``StandardScaler`` fitted on the training rows."""
    mean = X_train.mean(axis=0, dtype=np.float64)
    scale = np.sqrt(X_train.var(axis=0, dtype=np.float64))
    scale[scale < 10 * np.finfo(np.float64).eps] = 1.0
    to = lambda A: jnp.asarray(
        ((A - mean) / scale).astype(np.float32), dtype)
    return to(X_train), to(X_test)


def _chain(random_state):
    key = jax.random.PRNGKey(0 if random_state is None else
                             int(random_state))
    return jax.random.split(key)          # (the epochs' key, init_key)


def initial_weights(random_state, sizes, dtype=jnp.float32):
    """``[(W, b), ...]`` by the configuration's written rule."""
    _, init_key = _chain(random_state)
    layers = []
    for k, fan_in, fan_out in zip(
            jax.random.split(init_key, len(sizes) - 1), sizes[:-1],
            sizes[1:]):
        bound = jnp.sqrt(6.0 / (fan_in + fan_out)).astype(jnp.float32)
        kw, kb = jax.random.split(k)
        layers.append((
            jax.random.uniform(kw, (fan_in, fan_out), jnp.float32,
                               -bound, bound).astype(dtype),
            jax.random.uniform(kb, (fan_out,), jnp.float32,
                               -bound, bound).astype(dtype)))
    return layers


def epoch_orders(random_state, n_rows, n_train, epochs):
    """``(epochs, n_train)``: the position among the fold's training rows
    that each epoch visits first, second, ..."""
    key, _ = _chain(random_state)
    orders = []
    for _ in range(epochs):
        key, ek = jax.random.split(key)
        u = np.asarray(jax.random.uniform(ek, (n_rows,)))
        orders.append(np.argsort(u[:n_train], kind="stable"))
    return np.stack(orders)


def forward(layers, X):
    """The network's output before the softmax."""
    h = X
    for W, b in layers[:-1]:
        h = jax.nn.relu(h @ W + b)
    W, b = layers[-1]
    return h @ W + b


def batch_loss(layers, alpha, Xb, Yb, wb):
    """scikit-learn's ``_backprop`` loss of one minibatch; ``wb`` is 1 on
    its rows and 0 on the slots the epoch's last batch leaves empty."""
    rows = jnp.sum(wb)
    logp = jax.nn.log_softmax(forward(layers, Xb), axis=1)
    data = -jnp.sum(wb * jnp.sum(Yb * logp, axis=1)) / rows
    return data + 0.5 * alpha * sum(
        jnp.sum(W * W) for W, _ in layers) / rows


def adam_step(layers, grads, m, v, t, s):
    """``AdamOptimizer._get_updates`` and the update itself."""
    tm = jax.tree_util.tree_map
    dtype = layers[0][0].dtype
    b1, b2 = s["beta_1"], s["beta_2"]
    m = tm(lambda m_, g: (b1 * m_ + (1 - b1) * g).astype(dtype), m, grads)
    v = tm(lambda v_, g: (b2 * v_ + (1 - b2) * g * g).astype(dtype), v,
           grads)
    lr_t = s["learning_rate_init"] * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    layers = tm(lambda p, m_, v_: (
        p - lr_t * m_ / (jnp.sqrt(v_) + s["epsilon"])).astype(dtype),
        layers, m, v)
    return layers, m, v


@functools.partial(jax.jit, static_argnames=("frozen",))
def _fit_fold(layers, alphas, X_train, Y_train, batches, weights, frozen):
    """Every alpha's network through all epochs' minibatches.
    ``batches`` (steps, batch) are rows of ``X_train``, ``weights`` which
    of their slots hold one."""
    s = dict(frozen)

    def one_alpha(alpha):
        zeros = jax.tree_util.tree_map(jnp.zeros_like, layers)

        def step(carry, batch):
            p, m, v, t = carry
            idx, wb = batch
            grads = jax.grad(batch_loss)(
                p, alpha, X_train[idx], Y_train[idx], wb)
            t = t + 1.0
            p, m, v = adam_step(p, grads, m, v, t, s)
            return (p, m, v, t), None

        (p, _, _, _), _ = jax.lax.scan(
            step, (layers, zeros, zeros, jnp.float32(0.0)),
            (batches, weights))
        return p

    with jax.default_matmul_precision("highest"):
        return jax.vmap(one_alpha)(alphas)


@jax.jit
def _outputs(layers, X_test):
    with jax.default_matmul_precision("highest"):
        return jax.vmap(lambda p: forward(p, X_test))(layers)


def _accuracy(layers, X_test, y_test):
    """Each network's share of test rows whose largest output is the
    row's class.  The outputs come to the host and numpy takes the
    argmax: inside the compiled function XLA:TPU fuses it into the
    product and, for networks side by side, gets it wrong (8 of 12
    networks of 92 % accuracy read 10 %; PERF.md, PR 33)."""
    outputs = np.asarray(_outputs(layers, X_test).astype(jnp.float32))
    return (outputs.argmax(axis=-1) == np.asarray(y_test)[None, :]).mean(
        axis=1)


def fit_fold(X_train, y_train, n_rows, n_classes, s, alphas,
             dtype=jnp.float32, layers=None):
    """The fitted networks of every alpha (leaves with a leading alpha
    axis) on rows that are standardised already; ``n_rows`` is the data
    set's, which the epochs' order is drawn over.  ``layers``: other
    initial weights than the rule's.  ``shuffle=False`` walks the rows in
    order, as scikit-learn does."""
    n_train, d = X_train.shape
    batch = s["batch_size"]
    batch = min(200, n_train) if batch == "auto" else min(batch, n_train)
    epochs = int(s["max_iter"])
    if s["shuffle"]:
        orders = epoch_orders(s["random_state"], n_rows, n_train, epochs)
    else:
        orders = np.tile(np.arange(n_train), (epochs, 1))
    steps = -(-n_train // batch)
    slots = np.zeros((epochs, steps * batch), np.int32)
    slots[:, :n_train] = orders
    weights = np.zeros((epochs, steps * batch), np.float32)
    weights[:, :n_train] = 1.0
    if layers is None:
        layers = initial_weights(
            s["random_state"], (d, *s["hidden_layer_sizes"], n_classes),
            dtype)
    frozen = tuple(sorted((k, float(s[k])) for k in (
        "beta_1", "beta_2", "epsilon", "learning_rate_init")))
    return _fit_fold(
        layers, jnp.asarray(alphas, dtype), jnp.asarray(X_train, dtype),
        jnp.asarray(np.eye(n_classes, dtype=np.float32)[y_train], dtype),
        jnp.asarray(slots.reshape(epochs * steps, batch)),
        jnp.asarray(weights.reshape(epochs * steps, batch), dtype),
        frozen=frozen)


def mlp_cv_scores(X, y, splits, candidates, config, dtype=jnp.float32,
                  weights=False):
    """Test accuracy of every (candidate, fold), ``(len(candidates),
    len(splits))``, and the epochs each fit ran.  ``candidates`` are
    parameter dicts with ``mlp__`` keys.  With ``weights`` a third value:
    every candidate's fitted layers on the LAST fold."""
    classes, y_enc = np.unique(y, return_inverse=True)
    if len(classes) < 3:
        raise ValueError("this reference knows three or more classes "
                         "(scikit-learn's two-class net ends in one "
                         "logistic unit)")
    settings = [settings_of(config, c) for c in candidates]
    by_shape = {}
    for at, s in enumerate(settings):
        key = tuple(sorted((k, repr(v)) for k, v in s.items()
                           if k != "alpha"))
        by_shape.setdefault(key, []).append(at)
    scores = np.empty((len(candidates), len(splits)))
    epochs = np.zeros((len(candidates), len(splits)), np.int64)
    last = {}
    for f, (train, test) in enumerate(splits):
        X_train, X_test = standardise(X[train], X[test], dtype)
        y_test = jnp.asarray(y_enc[test])
        for members in by_shape.values():
            s = settings[members[0]]
            fitted = fit_fold(
                X_train, y_enc[train], len(X), len(classes), s,
                [settings[i]["alpha"] for i in members], dtype)
            scores[members, f] = np.asarray(
                _accuracy(fitted, X_test, y_test), np.float64)
            epochs[members, f] = int(s["max_iter"])
            if weights and f == len(splits) - 1:
                for j, i in enumerate(members):
                    last[i] = jax.tree_util.tree_map(
                        lambda a, j=j: np.asarray(a[j], np.float32), fitted)
    if weights:
        return scores, epochs, [last[i] for i in range(len(candidates))]
    return scores, epochs
