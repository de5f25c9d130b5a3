"""Operations and bytes a perceptron search *requires*, from its shapes and
the epochs it ran.  Kept with the benchmark so that no later change to the
program can move them.

What is counted is what the algorithm needs and no more: scikit-learn's
minibatch Adam on ``hidden_layer_sizes`` relu layers.  An epoch of one fit
passes each of the fold's training rows through the net once, forward and
backward: three products with every weight matrix a row (the layer's
output, the gradient of the weights, the delta handed down; the first
layer's delta is counted too, as the usual 6 a weight a row does), at two
operations a multiply-add: ``6 * rows * weights``.  Masked-out test rows,
a step's padding and padded lanes are the program's choice and count for
nothing.

Bytes of a step of one fit: Adam reads and writes the weights and both
moments once, 24 bytes a parameter in float32.  The gradient may stay on
the chip in a sound fused step and is not counted, nor is a second read of
the weights by the products.  Every fit of a fold may share one read of
the fold's minibatch (``batch * features`` floats a step and fold): the
candidates of a compile group train on the same rows.

The hidden shapes come from the program's own record of its compile groups
(``search_report["per_group"][g]["static_params"]``, the statics as the
search handed them over, and ``["geometry"]["groups"][g]["n_candidates"]``),
which a program from before the MLP counters writes too; the epochs from
``epochs_per_candidate``, or ``max_iter`` where the report has no such
series and no stopping rule can fire within ``max_iter`` epochs.

``mlp_adam_minibatch`` is the configuration's ``work``: it takes the
configuration, the number of candidates and one search's ``search_report``
and returns ``flops``, ``fit_flops`` and ``fit_bytes`` (the last two: the
minibatch steps alone, what ``sst.mlp.*`` runs) — or ``None`` where the
report does not name every candidate's shape.
"""

from __future__ import annotations

import ast

F32 = 4
ADAM_BYTES = 6 * F32        # weights and two moments, read and written
N_ITER_NO_CHANGE = 10       # scikit-learn's default


def group_shapes(report, n_candidates):
    """``[(hidden shape, candidates), ...]`` of the search's compile
    groups, or ``None`` where the report does not hold them for every
    candidate."""
    groups = report.get("geometry", {}).get("groups") or []
    per_group = report.get("per_group") or {}
    out = []
    for g in groups:
        rec = per_group.get(g["group"], per_group.get(str(g["group"])))
        try:
            static = ast.literal_eval(rec["static_params"])
        except (TypeError, KeyError, ValueError, SyntaxError):
            return None
        hidden = [v for k, v in static.items()
                  if k.split("__")[-1] == "hidden_layer_sizes"]
        if len(hidden) != 1:
            return None
        shape = hidden[0] if isinstance(hidden[0], (list, tuple)) \
            else [hidden[0]]
        out.append((tuple(int(h) for h in shape), int(g["n_candidates"])))
    if sum(n for _, n in out) != n_candidates:
        return None
    return out


def epochs_of(config, report, n_candidates):
    """Epochs each candidate ran, in ``cv_results_`` order."""
    epochs = report.get("epochs_per_candidate")
    if epochs:
        if len(epochs) != n_candidates or min(epochs) < 0:
            return None
        return [int(e) for e in epochs]
    params = config["estimator"]["params"]
    max_iter = int(params.get("max_iter", 200))
    if max_iter > int(params.get("n_iter_no_change", N_ITER_NO_CHANGE)) \
            or params.get("early_stopping"):
        return None        # a rule could have fired: no count, no guess
    return [max_iter] * n_candidates


def layer_counts(d, hidden, k):
    """(weights, weights + intercepts) of one net."""
    sizes = (d, *hidden, k)
    weights = sum(a * b for a, b in zip(sizes[:-1], sizes[1:]))
    return weights, weights + sum(sizes[1:])


def mlp_adam_minibatch(config, n_candidates, report):
    data = config["data"]
    n, d, k = data["n_samples"], data["n_features"], data["n_classes"]
    n_folds = data["n_folds"]
    n_test = n // n_folds
    n_train = n - n_test
    params = config["estimator"]["params"]
    batch = params.get("batch_size", "auto")
    batch = min(200, n_train) if batch == "auto" else min(int(batch),
                                                          n_train)
    steps_an_epoch = -(-n_train // batch)
    shapes = group_shapes(report, n_candidates)
    epochs = epochs_of(config, report, n_candidates)
    if shapes is None or epochs is None:
        return None
    fit_flops = fit_bytes = score_flops = 0.0
    at = 0
    for hidden, n_cand in shapes:
        weights, parameters = layer_counts(d, hidden, k)
        # candidates are in ParameterGrid order and a group's may be
        # anywhere in it; with no stopping rule firing they are all alike
        group_epochs = epochs[at:at + n_cand]
        at += n_cand
        fit_epochs = float(sum(group_epochs)) * n_folds
        fit_flops += 6.0 * n_train * weights * fit_epochs
        fit_bytes += (ADAM_BYTES * parameters * steps_an_epoch * fit_epochs
                      + float(max(group_epochs)) * n_folds * steps_an_epoch
                      * batch * d * F32)
        score_flops += 2.0 * n_test * weights * n_cand * n_folds
    scaler_flops = 5.0 * n * d * n_folds
    return {"flops": fit_flops + score_flops + scaler_flops,
            "fit_flops": fit_flops, "fit_bytes": fit_bytes}
