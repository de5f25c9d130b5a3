"""Operations and bytes a search *requires*, from its shapes alone.

Kept with the benchmark so that no later change to the program can move
them.  What is counted is what the algorithm needs: real lanes only (a
padding lane computes nothing anyone asked for), the training rows of a
fold only (the program keeps all rows and masks; the masked rows are its
choice, not the algorithm's), and the classes as they are (the chip pads
10 to 16; that is its layout's cost).

A configuration names its model of work as ``"module:function"``; the
function takes the configuration, the number of candidates and one
search's ``search_report``, and returns a dict with ``flops``,
``fit_flops`` and ``fit_bytes`` — or ``None`` where the report does not
hold the counts it needs, so that no share is ever made of a guess.
"""

from __future__ import annotations

import json
import os

F32 = 4


def fold_rows(n_samples, n_folds):
    """(training rows, test rows) of one fold."""
    n_test = n_samples // n_folds
    return n_samples - n_test, n_test


def iter_lanes(report):
    """(sum over launches of executed iterations x real lanes, sum of the
    launches' iterations) from a ``search_report``'s series, or ``None``
    where it does not hold them."""
    iters = report.get("solver_iters_per_launch")
    lanes = report.get("lanes_per_launch")
    if not iters or not lanes or len(iters) != len(lanes):
        return None
    return sum(i * n for i, n in zip(iters, lanes)), sum(iters)


def glm_softmax_lbfgs(config, n_candidates, report):
    """Multinomial logistic regression by L-BFGS, scored by accuracy.
    Padding lanes are not in the report's ``lanes_per_launch``.

    Per lane and executed iteration one loss-and-gradient: two products
    of the (n_train, d) rows with (d, k) — logits along the direction,
    and the gradient's pull-back — at 2 flops a multiply-add, so
    4 * n_train * d * k.  Scoring a fit: 2 * n_test * d * k.

    Bytes the fit needs per iteration: the rows twice for a whole launch
    (once per product; every lane of a launch shares them, so
    ``launch_iters`` = sum over launches of the iterations each ran), and
    per lane six passes over its (n_train, k) float32 logits: the
    direction's logits written, logits and direction's logits read by the
    line search, the new logits written, the loss's gradient written and
    read back by the pull-back.  The two-loop recursion's (history, k*d)
    vectors are 1% of that and are left out."""
    counts = iter_lanes(report)
    if counts is None:
        return None
    lane_iters, launch_iters = counts
    data = config["data"]
    d, k = data["n_features"], data["n_classes"]
    n_folds = data["n_folds"]
    n_train, n_test = fold_rows(data["n_samples"], n_folds)
    fit_flops = 4.0 * n_train * d * k * lane_iters
    score_flops = 2.0 * n_test * d * k * n_candidates * n_folds
    fit_bytes = (2.0 * n_train * d * F32 * launch_iters
                 + 6.0 * n_train * k * F32 * lane_iters)
    return {"flops": fit_flops + score_flops, "fit_flops": fit_flops,
            "fit_bytes": fit_bytes}


def load_peaks(device_kind, path=None):
    """The chip's peaks by ``device_kind``; an unknown kind is an error."""
    path = path or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"device kind {device_kind!r} is not in {path}: add its "
            "published peaks with their source, do not assume them")
    return table[device_kind]


def roofline_seconds(flops, nbytes, peaks):
    """(least seconds the chip needs, which of the two bounds it)."""
    by_flops = flops / peaks["flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), ("bytes" if by_bytes >= by_flops
                                     else "flops")
