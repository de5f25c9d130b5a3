"""Operations and bytes a kernel-SVM search *requires*, from its shapes and
its executed iteration counts.  Kept with the benchmark so that no later
change to the program can move them.

What is counted is what the algorithm needs and no more.  ``SVC`` on k
classes is k(k-1)/2 two-class duals a fold, each over THAT pair's own
training rows (m = 2/k of the fold's training rows on balanced classes):
the program keeps all n rows in every subproblem and masks, and the masked
rows are its choice, not the algorithm's.  An iteration of a dual is one
product of its own (m, m) Gram matrix with its alphas.

Bytes: the pairs' and folds' own matrices are all cut from the one (n, n)
kernel matrix of the candidate's gamma, and every entry of it belongs to
some (fold, pair); candidates that share a gamma, and all their folds and
pairs, may soundly advance on ONE read of it an iteration, so the least
traffic is one read an iteration of the slowest candidate of each distinct
gamma — at 2 bytes an element, the bfloat16 the configuration's precision
gives a float32 matrix product on the chip.  No sound re-ordering of the
work goes under that (a symmetric half would need the iteration's product
done twice over a triangle; not counted as sound here).

FLOPs: the dual iterations, 2 m^2 each; every pair of rows' squared
distance once for the whole search (n^2 d: gammas rescale it, folds and
pairs select from it; the exponentials are not counted); the pairs'
decisions on the test rows (2 n_test m a pair).

``svc_rbf_ovo_dual`` is the configuration's ``work``: it takes the
configuration, the number of candidates and one search's ``search_report``
and returns ``flops``, ``fit_flops`` and ``fit_bytes`` (the last two: the
dual iterations alone, what ``sst.box_fista.*`` runs) — or ``None`` where
the report holds neither every candidate's executed iterations nor, for an
unpadded search, the launches' sums of them (then ``fit_bytes`` alone is
``None``: the FLOPs need the sum, the reads each candidate's count).
"""

from __future__ import annotations

BF16 = 2


def candidate_iters(report, n_candidates):
    """Executed dual iterations of each candidate in ``cv_results_`` order,
    or ``None`` where the report lacks one."""
    iters = report.get("dual_iters_per_candidate")
    if not iters or len(iters) != n_candidates or min(iters) < 0:
        return None
    return [int(i) for i in iters]


def summed_iters(report, n_candidates, n_folds):
    """The candidates' executed iterations summed, from the launches' sums
    over tasks (a program that keeps no count a candidate has these): good
    only where no launch was padded, else ``None``."""
    sums = report.get("solver_iters_sum_per_launch")
    lanes = report.get("lanes_per_launch")
    if not sums or not lanes or sum(lanes) != n_candidates * n_folds \
            or min(sums) < 0:
        return None
    return sum(sums) / n_folds


def pair_rows(config):
    """(rows of one pair's own subproblem, test rows of a fold, pairs)."""
    data = config["data"]
    k, n_folds = data["n_classes"], data["n_folds"]
    n_test = data["n_samples"] // n_folds
    n_train = data["n_samples"] - n_test
    return 2.0 * n_train / k, n_test, k * (k - 1) // 2


def svc_rbf_ovo_dual(config, n_candidates, report, n_gammas=1):
    """``n_gammas``: how many distinct gammas the grid holds; candidates
    are in ParameterGrid order with gamma the last name, so candidate i
    has gamma number ``i % n_gammas``.  Left at 1 (every candidate on one
    read) the byte count is a bound that holds for any grid."""
    data = config["data"]
    n, d, n_folds = data["n_samples"], data["n_features"], data["n_folds"]
    iters = candidate_iters(report, n_candidates)
    total = (sum(iters) if iters is not None
             else summed_iters(report, n_candidates, n_folds))
    if total is None or n_candidates % n_gammas:
        return None
    m, n_test, n_pairs = pair_rows(config)
    duals = n_folds * n_pairs
    fit_flops = 2.0 * m * m * duals * total
    # the reads need each candidate's own count: no share of a roofline
    # is made of a guess
    fit_bytes = None if iters is None else float(
        sum(max(iters[g::n_gammas]) for g in range(n_gammas))) * n * n * BF16
    distance_flops = float(n) * n * d
    decision_flops = 2.0 * n_test * m * duals * n_candidates
    return {"flops": fit_flops + distance_flops + decision_flops,
            "fit_flops": fit_flops, "fit_bytes": fit_bytes}
