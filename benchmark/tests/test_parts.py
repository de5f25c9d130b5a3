"""The yardstick's own arithmetic: the generator, the comparison, the work
model against a hand count, the trace reduction against hand-made events
and a small trace recorded on a TPU v5e."""

import json
import os

import numpy as np
import pytest
from sklearn.model_selection import StratifiedKFold

import check
import generate
import trace_reduce
import work

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

SPEC = dict(n_samples=1000, n_features=12, n_classes=10, n_folds=5,
            latent=4, separation=0.6, pixel_noise=1.0, base_seed=3)


# -- generate ---------------------------------------------------------------

def test_data_is_the_configurations_and_balanced():
    X, y = generate.make_data(SPEC)
    assert X.dtype == np.float32 and X.shape == (1000, 12)
    assert X.min() >= 0.0 and X.max() <= 1.0
    assert y.dtype == np.int64
    assert np.all(np.bincount(y, minlength=10) == 100)
    X2, y2 = generate.make_data(SPEC)
    assert np.array_equal(X, X2) and np.array_equal(y, y2)
    assert not np.array_equal(
        X, generate.make_data(dict(SPEC, base_seed=4))[0])


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345])
def test_every_seed_is_the_same_grid_in_another_order(seed):
    traffic = json.load(open(os.path.join(BENCH, "traffic", "grid300.json")))
    grid = generate.expand_grid(traffic["param_grid"], seed)
    assert len(grid["C"]) == 300
    assert np.allclose(np.sort(grid["C"]), np.logspace(-4, 3, 300))
    assert grid == generate.expand_grid(traffic["param_grid"], seed)
    assert grid["C"] != generate.expand_grid(traffic["param_grid"],
                                             seed + 1)["C"]
    assert grid["C"] != sorted(grid["C"])


def test_factory_builds_the_public_call_from_files():
    traffic = json.load(open(os.path.join(BENCH, "traffic", "grid300.json")))
    config = json.load(open(os.path.join(BENCH, "configs",
                                         "logreg_mnist.json")))
    new_search, grid = generate.search_factory(config, traffic, 5)
    search = new_search()
    assert type(search).__name__ == "GridSearchCV"
    assert search.param_grid == grid
    assert search.estimator.max_iter == 100 and search.refit is False
    assert new_search() is not search


# -- check ------------------------------------------------------------------

def _cv_results(scores, grid):
    mean = scores.mean(axis=1)
    from scipy.stats import rankdata
    res = {f"split{i}_test_score": scores[:, i]
           for i in range(scores.shape[1])}
    res.update(params=check.candidates_of(grid), mean_test_score=mean,
               rank_test_score=rankdata(-mean, method="min"))
    return res


def test_sample_holds_both_ends_and_follows_the_seed():
    a = check.draw_sample(300, 24, 5)
    assert len(a) == 26 and a[0] == 0 and a[-1] == 299
    assert np.array_equal(a, check.draw_sample(300, 24, 5))
    assert not np.array_equal(a, check.draw_sample(300, 24, 6))
    assert list(check.draw_sample(3, 24, 1)) == [0, 1, 2]
    # the well-defined region is compared in full, under every seed
    b = check.draw_sample(300, 10, 7, always=range(40, 60))
    assert len(b) == 32 and set(range(40, 60)) <= set(b)
    assert {0, 299} <= set(b)


def test_compare_counts_structure_and_gaps():
    grid = {"C": [0.1, 1.0, 10.0, 100.0]}
    rng = np.random.default_rng(0)
    scores = rng.uniform(0.5, 0.9, (4, 5))
    candidates = check.candidates_of(grid)
    sample = np.array([0, 3])
    limits = {"limits": {"mean_abs_gap": 1e-3, "max_abs_gap": 5e-3,
                         "converged_bias": 1e-4},
              "well_defined": {"param": "C", "max": 0.1}}
    good = _cv_results(scores, grid)
    compared, correct = check.compare([good, good], candidates, sample,
                                      scores[sample], limits)
    assert correct and compared["structure"]["value"] == 0
    assert compared["max_abs_gap"]["value"] == 0.0
    # no search in the window: nothing was proved
    assert check.compare([], candidates, sample, scores[sample],
                         limits)[1] is False
    # a wrong answer in the SECOND search is seen
    moved = scores.copy()
    moved[3, 2] += 0.02
    compared, correct = check.compare(
        [good, _cv_results(moved, grid)], candidates, sample,
        scores[sample], limits)
    assert not correct
    assert compared["max_abs_gap"]["value"] == pytest.approx(0.02)
    assert list(compared) == ["structure", "mean_abs_gap", "max_abs_gap",
                              "converged_bias"]
    # gaps of either sign cancel in the bias; a shift does not.  Only the
    # candidates in the well-defined region (here C <= 0.1) count for it
    noisy = scores.copy()
    noisy[0] += np.array([4e-4, -4e-4, 4e-4, -4e-4, 0.0])
    compared, correct = check.compare(
        [_cv_results(noisy, grid)], candidates, sample, scores[sample],
        limits)
    assert correct and compared["converged_bias"]["value"] < 1e-12
    shifted = scores.copy()
    shifted[0] -= 4e-4
    compared, correct = check.compare(
        [_cv_results(shifted, grid)], candidates, sample, scores[sample],
        limits)
    assert not correct
    assert compared["converged_bias"]["value"] == pytest.approx(4e-4)
    assert compared["mean_abs_gap"]["value"] == pytest.approx(2e-4)
    # a number without a limit in the configuration is not compared
    only = {"limits": {"max_abs_gap": 5e-3}}
    assert list(check.compare([good], candidates, sample, scores[sample],
                              only)[0]) == ["structure", "max_abs_gap"]
    # a mean that does not follow from its splits, a score that is no
    # number, candidates out of the grid's order
    bad = _cv_results(scores, grid)
    bad["mean_test_score"] = bad["mean_test_score"] + 0.01
    assert check.structure_violations(bad, candidates, 5) >= 4
    nan = scores.copy()
    nan[1, 1] = np.nan
    assert check.failed_fits(_cv_results(nan, grid), 5) == 1
    assert check.structure_violations(_cv_results(nan, grid),
                                      candidates, 5) >= 1
    shuffled = _cv_results(scores, grid)
    shuffled["params"] = shuffled["params"][::-1]
    assert check.structure_violations(shuffled, candidates, 5) == 4


def test_control_in_lower_precision_reads_not_correct():
    """The control: the reference put in the program's place and computed
    in bfloat16, the nearest precision below the configuration's float32.
    At the rehearsal's size, against the rehearsal's limits, it has to
    come out not correct (the chip readings at the cells' own sizes are in
    PERF.md; ``readings.py`` takes them)."""
    import jax.numpy as jnp

    import reference
    config = json.load(open(os.path.join(HERE, "tiny_logreg.json")))
    traffic = json.load(open(os.path.join(HERE, "tiny_grid.json")))
    grid = generate.expand_grid(traffic["param_grid"], 2**31 + 3)
    X, y = generate.make_data(config["data"])
    splits = list(StratifiedKFold(5).split(X, y))
    candidates, sample, ref = check.reference_sample(
        grid, X, y, splits, config, 2**31 + 3,
        reference.logistic_cv_scores)
    assert 0.5 < ref.mean() < 0.99      # a problem, not noise or a gift
    control, _ = reference.logistic_cv_scores(
        X, y, splits, [candidates[i] for i in sample], config,
        dtype=jnp.bfloat16)
    answers = np.full((len(candidates), 5), ref.mean())
    answers[sample] = control
    compared, correct = check.compare(
        [_cv_results(answers, grid)], candidates, sample, ref,
        config["check"])
    assert not correct
    assert compared["structure"]["value"] == 0
    assert compared["mean_abs_gap"]["value"] > \
        3 * config["check"]["limits"]["mean_abs_gap"]
    # and the reference against itself is exact
    same = answers.copy()
    same[sample] = ref
    assert check.compare([_cv_results(same, grid)], candidates, sample,
                         ref, config["check"])[1]


# -- work -------------------------------------------------------------------

def test_work_against_a_hand_count():
    config = {"data": {"n_samples": 70000, "n_features": 784,
                       "n_classes": 10, "n_folds": 5}}
    # 8 launches of 190 lanes, every one run for 100 iterations; 300
    # candidates.  By hand: n_train 56 000, n_test 14 000.
    report = {"lanes_per_launch": [190] * 8,
              "solver_iters_per_launch": [100] * 8}
    needs = work.glm_softmax_lbfgs(config, 300, report)
    assert work.glm_softmax_lbfgs(config, 300, {}) is None
    assert needs["fit_flops"] == 4 * 56000 * 784 * 10 * 152000
    assert needs["flops"] - needs["fit_flops"] == \
        2 * 14000 * 784 * 10 * 1500
    assert needs["fit_bytes"] == (2 * 56000 * 784 * 4 * 800
                                  + 6 * 56000 * 10 * 4 * 152000)
    peaks = work.load_peaks("TPU v5 lite")
    least, bound = work.roofline_seconds(needs["fit_flops"],
                                         needs["fit_bytes"], peaks)
    assert bound == "bytes"
    assert least == pytest.approx(needs["fit_bytes"] / 819e9)
    with pytest.raises(KeyError):
        work.load_peaks("cpu")


# -- trace_reduce -----------------------------------------------------------

def test_busy_idle_and_gaps_by_hand():
    ops = [("a", 0.0, 1.0), ("b", 0.5, 1.0), ("a", 3.0, 0.5),
           ("c", 3.5005, 0.4995)]
    assert trace_reduce.union_intervals(ops) == [[0.0, 1.5], [3.0, 3.5],
                                                 [3.5005, 4.0]]
    assert trace_reduce.busy_seconds(ops) == pytest.approx(2.4995)
    assert trace_reduce.gaps(ops, -1.0, 5.0) == [
        (-1.0, 0.0), (1.5, 3.0), (3.5, 3.5005), (4.0, 5.0)]
    assert trace_reduce.top_ops(ops, 2) == [["a", 1.5], ["b", 1.0]]
    host = [("bench.search", 0.0, 4.2), ("PjitFunction(fit)", 1.4, 1.7),
            ("$python frame", 0.0, 4.2)]
    trace = {"devices": {0: {"ops": ops}}, "host": host}
    red = trace_reduce.reduce(trace, 1)
    assert red["window_s"] == pytest.approx(4.2)
    assert red["busy_s"] == pytest.approx(2.4995)
    gaps = dict(red["idle_gaps"])
    assert gaps["inside_fit:PjitFunction_fit_"] == pytest.approx(1.5)
    assert gaps["gaps_under_2_ms"] == pytest.approx(0.0005)
    assert gaps["inside_fit:host_code_outside_any_jax_call"] == \
        pytest.approx(0.2)
    # two devices, one idle: busy is the average over the chips used
    trace["devices"][1] = {"ops": []}
    assert trace_reduce.reduce(trace, 2)["busy_s"] == \
        pytest.approx(2.4995 / 2)
    # no device plane (XLA:CPU): nothing, never a zero
    assert trace_reduce.reduce({"devices": {}, "host": host}, 1) is None
    # time between two searches is named for it
    spans = [(0.0, 1.0), (2.0, 3.0)]
    assert trace_reduce.name_gap((1.0, 2.0), spans, []) == \
        "between_searches"


def test_recorded_tpu_trace():
    path = os.path.join(HERE, "small_tpu_trace.xplane.pb")
    trace = trace_reduce.load(path)
    assert list(trace["devices"]) == [0]
    red = trace_reduce.reduce(trace, 1)
    assert 0 < red["busy_s"] < red["window_s"]
    assert len(trace_reduce.search_spans(trace["host"])) == 2
    names = dict(red["idle_gaps"])
    assert "between_searches" in names
    assert red["device_ops"] and all(s > 0 for _, s in red["device_ops"])


# -- the files a cell needs are there ---------------------------------------

def test_every_name_in_benchmark_json_has_its_file():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in bench["workloads"]:
        assert os.path.isfile(os.path.join(
            BENCH, "traffic", w["traffic"] + ".json"))
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "layers",
                                           m["name"] + ".py")), m["name"]
    # the four-chip cell of PERF.md's first open question needs no file
    # but its workloads entry
    assert os.path.isfile(os.path.join(BENCH, "configs",
                                       "logreg_mnist.json"))
    assert os.path.isfile(os.path.join(BENCH, "traffic", "grid1000.json"))
