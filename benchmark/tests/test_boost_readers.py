"""The boosting cell's per-layer readers and work model: on the committed
scoped trace of a TPU (a logistic-regression search: no ``sst.boost.*``
scope, so every device reader says nothing and none raises), on a reduction
that holds the stage loop's scopes, and on ``search_report``s with and
without the boosters' counters."""

import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

import run              # noqa: E402
import scopes           # noqa: E402
import trace_reduce     # noqa: E402
import work             # noqa: E402
import work_boost       # noqa: E402

SCOPED = os.path.join(HERE, "scoped_tpu_trace.xplane.pb")
CELL = "gbc_covtype145k.lr5_stages3"
DEVICE = ("boost.device_s", "boost.hist_share", "boost.partition_share",
          "boost.stage_share", "boost_histogram_roofline")
COUNTED = ("boost.tree_steps", "boost.idle_stage_share")
CONFIG = run.load_json(os.path.join(BENCH, "configs",
                                    "gbc_covtype145k.json"))
COUNTS = [25, 50, 100] * 5          # cv_results_ order: rate-major
# the cell's search as the program reports it: a launch a count
REPORT = {"trees_per_candidate": COUNTS,
          "tree_steps_per_launch": [25 * 25, 25 * 50, 25 * 100]}
# ... and as one lockstep launch of all 75 lanes would
LOCKSTEP = {"trees_per_candidate": COUNTS, "tree_steps_per_launch": [7500]}


def reader(name):
    return run.load_file(os.path.join(BENCH, "layers", name + ".py")).read


def ctx_with(report, reduced=None, trace=None):
    return {"config": CONFIG, "report": report, "reports": [report],
            "n_candidates": 15, "fits_per_search": 75, "chips": 1,
            "device": {"platform": "tpu", "kind": "TPU v5 lite"},
            "trace": trace, "work": work, "cell": {"name": "a.cell"},
            "window_s": 40.0,
            "load_named": lambda spec: (
                (lambda ctx: reduced) if spec == "scopes:read"
                else run.load_named(spec, BENCH))}


def test_the_benchmark_declares_the_cell_and_its_metrics():
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    cell, config, traffic = run.find_cell(bench, CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "gbc_covtype145k", "lr5_stages3")
    assert config["architecture"] is None
    assert traffic["param_grid"] == {
        "learning_rate": [0.025, 0.05, 0.1, 0.2, 0.4],
        "n_estimators": [25, 50, 100]}
    # the configuration's widths state the grid the work is counted from
    assert config["widths"]["learning_rate"] == \
        traffic["param_grid"]["learning_rate"]
    assert config["widths"]["n_estimators"] == \
        traffic["param_grid"]["n_estimators"]
    mine = {m["name"]: m for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert set(mine) == set(DEVICE + COUNTED)
    assert {m["moves"] for m in mine.values()} == {"search_wall_s"}
    assert {m["layer"] for m in mine.values()} == {"solvers"}
    for name in mine:
        assert os.path.exists(os.path.join(BENCH, "layers", name + ".py"))
    # no accepted tree metric took the cell on
    assert not [m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", ()) and m["name"] not in mine]


def test_committed_trace_of_another_family_reads_as_nothing(
        tmp_path, monkeypatch, capsys):
    run_dir = tmp_path / "trace" / "plugins" / "profile" / "t0"
    run_dir.mkdir(parents=True)
    shutil.copyfile(SCOPED, run_dir / "vm.xplane.pb")
    monkeypatch.setenv("BENCH_TEST_TRACE_DIR", str(tmp_path / "trace"))
    monkeypatch.setattr(scopes, "_PARSED", {})
    ctx = ctx_with(REPORT, trace=trace_reduce.reduce(
        trace_reduce.load(SCOPED), 1))
    ctx["load_named"] = lambda spec: run.load_named(spec, BENCH)
    assert {name: reader(name)(ctx) for name in DEVICE} == \
        dict.fromkeys(DEVICE)
    assert "no sst.boost.* scope" in capsys.readouterr().out


@pytest.mark.parametrize("name", DEVICE)
def test_no_trace_reads_as_nothing(name):
    assert reader(name)(ctx_with(REPORT)) is None


@pytest.mark.parametrize("name", DEVICE)
def test_a_forests_trace_reads_as_nothing(name):
    """``sst.tree.*`` without ``sst.boost.*`` is a forest's search."""
    reduced = {"scopes": {"sst.tree.histogram": 10.0,
                          "sst.tree.bootstrap": 1.0, "sst.fit": 2.0}}
    assert reader(name)(ctx_with(REPORT, reduced)) is None


def test_device_readers_on_the_stage_loops_scopes(capsys):
    reduced = {"scopes": {
        "sst.boost.gradient": 1.0, "sst.boost.update": 0.5,
        "sst.tree.partition": 3.0, "sst.tree.histogram": 10.0,
        "sst.tree.split": 3.5, "sst.tree.route": 1.5,
        "sst.tree.predict": 0.5, "sst.fit": 2.0, "sst.score": 0.25,
        "unscoped": 0.125}}
    ctx = ctx_with(REPORT, reduced)
    assert reader("boost.device_s")(ctx) == pytest.approx(20.0)
    assert reader("boost.hist_share")(ctx) == pytest.approx(50.0)
    assert reader("boost.partition_share")(ctx) == pytest.approx(15.0)
    assert reader("boost.stage_share")(ctx) == pytest.approx(7.5)
    needs = work_boost.boost_histograms(CONFIG, 15, REPORT)
    share = reader("boost_histogram_roofline")(ctx)
    assert share == pytest.approx(
        100.0 * needs["fit_bytes"] / 819e9 / 10.0)
    assert 0.0 < share < 100.0
    assert "bound by bytes" in capsys.readouterr().out


@pytest.mark.parametrize("report,steps,idle", [
    (REPORT, 4375, 0.0),
    (LOCKSTEP, 7500, 100.0 * (1.0 - 4375.0 / 7500.0)),
])
def test_counter_readers(report, steps, idle):
    ctx = ctx_with(report)
    assert reader("boost.tree_steps")(ctx) == steps
    assert reader("boost.idle_stage_share")(ctx) == pytest.approx(idle)


@pytest.mark.parametrize("name", COUNTED)
def test_a_program_from_before_the_counters_reads_as_nothing(name):
    assert reader(name)(ctx_with({"lanes_per_launch": [10] * 8,
                                  "solver_iters_per_launch": [25] * 8})
                        ) is None


def test_work_is_counted_from_the_grid_and_not_from_what_ran():
    """2 500 distinct stages a search (5 folds x 5 learning rates x the
    largest count), whatever the program executed."""
    needs = work_boost.boost_histograms(CONFIG, 15, REPORT)
    assert work_boost.distinct_stages(CONFIG, 15) == 500
    n_train = 145253 - 145253 // 5
    assert needs["fit_flops"] == pytest.approx(
        2500 * 3 * n_train * 54 * 2)
    assert needs["fit_bytes"] == pytest.approx(
        2500 * (3 * n_train * (54 + 8) + 7 * 54 * 256 * 2 * 4))
    assert needs["flops"] > needs["fit_flops"]
    for report in (LOCKSTEP, {}, None,
                   {"tree_steps_per_launch": [2500]}):
        assert work_boost.boost_histograms(CONFIG, 15, report) == needs


def test_work_model_makes_no_share_of_another_grid():
    assert work_boost.boost_histograms(CONFIG, 14, REPORT) is None


def test_search_mfu_reads_the_cells_work():
    ctx = ctx_with(REPORT)
    share = reader("search.mfu")(ctx)
    needs = work_boost.boost_histograms(CONFIG, 15, REPORT)
    assert share == pytest.approx(100.0 * needs["flops"] / (40.0 * 197e12))
    assert 0.0 < share < 100.0


def test_the_configuration_states_what_the_issue_asks():
    assert CONFIG["reduced"] == ["data.n_samples: 581012 -> 145253"]
    assert CONFIG["data"]["n_classes"] == 2
    widths = CONFIG["widths"]
    assert (widths["n_features"], widths["n_bins"], widths["max_depth"],
            widths["trees_per_stage"], widths["parts_per_statistic"],
            widths["statistics_per_row"], widths["n_folds"]) == (
        54, 256, 3, 1, 3, 2, 5)
    assert CONFIG["estimator"] == {"class": "estimators_boost.boost",
                                   "params": {"random_state": 0}}
    for key in ("source", "deployment", "precision", "guarantees",
                "assumed", "reduced_why"):
        assert CONFIG[key]
    limits = CONFIG["check"]["limits"]
    assert set(limits) <= {"max_abs_gap", "mean_abs_gap", "converged_bias"}
    assert json.dumps(CONFIG)       # plain data
