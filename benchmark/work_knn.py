"""Operations and bytes a k-NN search *requires*, counted from the grid and
shapes the configuration states and from nothing the program ran.  Kept with
the benchmark so that no later change to the program can move them.

What is counted is what the algorithm needs and no more.  A test row of a
fold needs its distances to that fold's training rows (``2 d`` operations
each, one multiply-add a feature, the product's; the norms are ``2 d`` a row
once), ONE selection of the grid's largest ``n_neighbors`` among them,
and, for each weighting, ``max_k x classes`` additions of votes.  Every row
is a test row of one fold: ``n x n_train`` distances in all.  A program that
also computes a row's distances to its own fold's rows (to mask them), runs
a product at three bfloat16 passes, or selects once a fold does more than
this, by its own choice, and reads as a lower share.

Bytes: the rows read once and the neighbour lists (a float32 distance and an
int32 index a neighbour) written once.

``knn_neighbors`` is the configuration's ``work``: it takes the
configuration, the number of candidates and one search's ``search_report``
(unread: the signature is the harness's) and returns ``flops``,
``fit_flops`` and ``fit_bytes`` (the last two: the neighbour search alone,
what ``sst.knn.distance`` and ``sst.knn.select`` run) — or ``None`` where
the candidates are not the stated grid's.
"""

from __future__ import annotations

F32 = 4
WEIGHTINGS = 2


def knn_neighbors(config, n_candidates, report=None):
    data, widths = config["data"], config["widths"]
    n, d, n_folds = data["n_samples"], data["n_features"], data["n_folds"]
    ks = widths["n_neighbors"]
    if n_candidates != len(ks) * len(widths["weights"]):
        return None
    n_train = n - n // n_folds
    max_k = max(ks)
    fit_flops = 2.0 * n * n_train * d
    fit_bytes = float(n * d * F32 + n * max_k * 2 * F32)
    norm_flops = 2.0 * n * d
    vote_flops = float(WEIGHTINGS * n * max_k * data["n_classes"])
    return {"flops": fit_flops + norm_flops + vote_flops,
            "fit_flops": fit_flops, "fit_bytes": fit_bytes}
