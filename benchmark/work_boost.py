"""Operations and bytes a boosting search *requires*, counted from the grid
the configuration states and from nothing the program ran.  Kept with the
benchmark so that no later change to the program can move them.

What is counted is what the algorithm needs and no more.  Stage t of a fold
at a learning rate depends on the stages before it and on nothing else, so
the candidates of one learning rate are ONE run to the largest count, read
at the others: the DISTINCT stages of a search are ``folds x learning rates
x the largest n_estimators`` (2 500 of the 4 375 that 15 candidates x 5
folds count one by one).  A stage is one tree (two classes), grown level by
level on the fold's training rows: a level reads each training row once,
its ``d`` code bytes and its two float32 statistics (gradient, hessian), and
adds the two into one bin of each of the ``d`` features: ``2 d`` additions a
row a level; it writes the level's histograms once, ``nodes x d x 256 x 2``
float32.  A program that runs every candidate's own stages, carries lanes
past their count, builds both children of a node where one and a
subtraction would do, sorts rows or pads tiles does more than this, by its
own choice; one that shares the stages does exactly this.  The same number
whatever implements it: sharing stages, subtracting siblings or skipping a
sort later reads as a higher share, not as less work.

``boost_histograms`` is the configuration's ``work``: it takes the
configuration, the number of candidates and one search's ``search_report``
(unread: the signature is the harness's) and returns ``flops``,
``fit_flops`` and ``fit_bytes`` (the last two: the level histograms alone,
what ``sst.tree.histogram`` runs) — or ``None`` where the candidates are not
the stated grid's.
"""

from __future__ import annotations

N_BINS = 256
STATS = 2                    # a row's gradient and hessian
F32 = 4
STAGE_FLOPS = 12             # a row a stage: sigmoid, g, h, F += lr * leaf


def distinct_stages(config, n_candidates):
    """Distinct stages of one fold of the stated grid, or ``None`` where
    the search's candidates are not that grid's."""
    widths = config["widths"]
    rates, counts = widths["learning_rate"], widths["n_estimators"]
    if n_candidates != len(rates) * len(counts):
        return None
    return len(rates) * max(counts)


def boost_histograms(config, n_candidates, report=None):
    data, widths = config["data"], config["widths"]
    n, d, n_folds = data["n_samples"], data["n_features"], data["n_folds"]
    n_test = n // n_folds
    n_train = n - n_test
    stages = distinct_stages(config, n_candidates)
    if stages is None:
        return None
    depth = widths["max_depth"]
    trees = float(n_folds) * stages * widths["trees_per_stage"]
    nodes = 2 ** depth - 1                       # above the last level
    fit_flops = trees * depth * n_train * d * STATS
    fit_bytes = trees * (depth * n_train * (d + STATS * F32)
                         + nodes * d * N_BINS * STATS * F32)
    # between two trees: every row's mean, gradient, hessian and update
    stage_flops = float(n_folds) * stages * n * STAGE_FLOPS
    score_flops = float(n_folds) * n_candidates * n_test
    return {"flops": fit_flops + stage_flops + score_flops,
            "fit_flops": fit_flops, "fit_bytes": fit_bytes}
