"""The comparison that decides ``correct``: ``cv_results_`` of the searches
the window itself ran, at the timed sizes, against the plain reference.

Every search of the window is held to the reference, so an answer that
goes wrong in the second search is seen like one in the first.  The
reference fits, on every fold, every candidate that lies where the
configuration says the score is one well-defined number, both ends of the
grid, and ``check.n_candidates`` more drawn from the seed.  What is compared — each number that the
configuration's file gives a limit, beside that limit:

- ``structure``: violations counted over ALL answers of every search —
  the candidates in the grid's order, every split score finite and in
  [0, 1], ``mean_test_score`` the mean of its splits, ``rank_test_score``
  the order of the means.  Exact: the limit is 0.
- ``max_abs_gap`` and ``mean_abs_gap``: over the sampled candidates times
  every fold, the widest and the mean distance between the program's split
  score and the reference's for the same candidate and fold.  A score that
  lands at another candidate, a solver that did not move, a fold that was
  cut elsewhere all read here.
- ``converged_bias``: the size of the MEAN SIGNED gap over the sampled
  candidates that lie where the configuration says the score is one
  well-defined number (strong regularisation: both solvers reach the
  optimum within ``max_iter``).  There a sound program's gaps are a few
  flipped predictions of either sign and cancel; a precision below the
  configuration's leaves a bias that does not.
"""

from __future__ import annotations

import itertools
import sys

import numpy as np
from scipy.stats import rankdata


def candidates_of(grid):
    """Parameter dicts in the order sklearn's ParameterGrid yields them
    (names sorted, the last name varying fastest)."""
    names = sorted(grid)
    return [dict(zip(names, values))
            for values in itertools.product(*(grid[n] for n in names))]


def split_scores(cv_results, n_folds):
    """``(n_candidates, n_folds)`` test scores of one search."""
    return np.stack([np.asarray(cv_results[f"split{i}_test_score"],
                                np.float64) for i in range(n_folds)], axis=1)


def draw_sample(n_candidates, n_sample, seed, always=()):
    """Sorted candidate indices: ``always`` and both ends of the grid, and
    ``n_sample`` more drawn from the seed among the others (all of them
    where there are no more than that)."""
    fixed = np.union1d(np.asarray(always, int),
                       [0, n_candidates - 1][:n_candidates])
    others = np.setdiff1d(np.arange(n_candidates), fixed)
    rng = np.random.default_rng(int(seed))
    drawn = rng.choice(others, size=min(int(n_sample), len(others)),
                       replace=False)
    return np.union1d(fixed, drawn).astype(int)


def structure_violations(cv_results, candidates, n_folds):
    """How many answers of one search break what every search owes."""
    bad = 0
    params = list(cv_results["params"])
    if len(params) != len(candidates):
        return len(candidates)
    bad += sum(1 for got, want in zip(params, candidates)
               if dict(got) != want)
    try:
        scores = split_scores(cv_results, n_folds)
    except KeyError:
        return len(candidates) * n_folds
    bad += int(np.count_nonzero(
        ~(np.isfinite(scores) & (scores >= 0) & (scores <= 1))))
    mean = np.asarray(cv_results["mean_test_score"], np.float64)
    bad += int(np.count_nonzero(
        ~(np.abs(mean - scores.mean(axis=1)) <= 1e-6)))
    rank = np.asarray(cv_results["rank_test_score"])
    bad += int(np.count_nonzero(rank != rankdata(-mean, method="min")))
    return bad


def failed_fits(cv_results, n_folds):
    """Fits of one search whose split score is not a finite number."""
    try:
        return int(np.count_nonzero(
            ~np.isfinite(split_scores(cv_results, n_folds))))
    except KeyError:
        return len(cv_results.get("params", ())) * n_folds


def well_defined(candidates, spec):
    """Which candidates lie where the configuration calls the score one
    well-defined number: ``{"param": name, "max": value}``."""
    if not spec:
        return np.zeros(len(candidates), bool)
    return np.array([c[spec["param"]] <= spec["max"] for c in candidates])


def reference_sample(grid, X, y, splits, config, seed, reference_fn,
                     **reference_kwargs):
    """The grid's candidates, the compared indices and the reference's
    ``(len(sample), n_folds)`` split scores for them.  The reference runs
    in blocks of ``check.block`` candidates, sorted by their values so
    that lanes of one block stop at about the same iteration."""
    spec = config["check"]
    candidates = candidates_of(grid)
    region = well_defined(candidates, spec.get("well_defined"))
    sample = draw_sample(len(candidates), spec["n_candidates"], seed,
                         always=np.flatnonzero(region))
    by_value = sorted(range(len(sample)), key=lambda j: sorted(
        candidates[sample[j]].items()))
    block = int(spec.get("block", len(sample)))
    reference = np.empty((len(sample), len(splits)))
    for i in range(0, len(by_value), block):
        rows = by_value[i:i + block]
        reference[rows] = reference_fn(
            X, y, splits, [candidates[sample[j]] for j in rows], config,
            **reference_kwargs)[0]
    return candidates, sample, reference


def gap_numbers(scores, reference, region):
    """The gap's numbers for sampled split scores against the
    reference's; a score that is no number is infinitely far."""
    gap = np.asarray(scores, np.float64) - reference
    gap = np.where(np.isfinite(gap), gap, np.inf)
    out = {"max_abs_gap": float(np.abs(gap).max()),
           "mean_abs_gap": float(np.abs(gap).mean())}
    if region.any():
        out["converged_bias"] = float(abs(gap[region].mean()))
    return out


def compare(all_cv_results, candidates, sample, reference, spec):
    """``{name: {"value", "limit"}}`` for the window's searches — the
    largest value any search gives — and whether every value keeps to its
    limit.  ``spec`` is the configuration's ``check``."""
    n_folds = reference.shape[1]
    region = well_defined([candidates[i] for i in sample],
                          spec.get("well_defined"))
    values = {"structure": 0}
    for cv_results in all_cv_results:
        values["structure"] += structure_violations(cv_results, candidates,
                                                    n_folds)
        try:
            numbers = gap_numbers(
                split_scores(cv_results, n_folds)[sample], reference, region)
        except (KeyError, IndexError):
            numbers = dict.fromkeys(spec["limits"], float("inf"))
        for name, value in numbers.items():
            values[name] = max(values.get(name, 0.0), value)
    limits = dict(spec["limits"], structure=0)
    compared = {name: {"value": values.get(name, float("inf")),
                       "limit": limit}
                for name, limit in limits.items()}
    compared = {"structure": compared.pop("structure"), **compared}
    correct = bool(all_cv_results) and all(
        v["value"] <= v["limit"] for v in compared.values())
    return compared, correct


def print_compared(compared, correct, file=sys.stderr):
    """The last lines of standard error: each number beside its limit."""
    for name, v in compared.items():
        print(f"compared {name}: value={v['value']:.6g} "
              f"limit={v['limit']:.6g}", file=file)
    print(f"correct: {str(correct).lower()}", file=file, flush=True)
