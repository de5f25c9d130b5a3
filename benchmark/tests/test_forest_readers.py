"""The forest cell's per-layer readers and work model: on the committed
scoped trace of a TPU (a logistic-regression search: no ``sst.tree.*``
scope, so every device reader says nothing and none raises), on a
reduction that holds the tree grower's scopes, and on ``search_report``s
with and without the forest's counters."""

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
import work_forest      # noqa: E402

SCOPED = os.path.join(HERE, "scoped_tpu_trace.xplane.pb")
DEVICE = ("forest.device_s", "forest.hist_share", "forest.partition_share",
          "tree_histogram_roofline")
COUNTED = ("forest.idle_tree_share", "forest.tree_levels")
CONFIG = run.load_json(os.path.join(BENCH, "configs",
                                    "forest_covtype145k.json"))
# the cell's search as the program reports it: three depths, three counts
REPORT = {
    "trees_per_candidate": [10, 20, 40] * 3,
    "tree_slots_per_launch": [600, 600, 600],
    "tree_levels_per_launch": [3600, 4800, 6000],
    "per_group": {str(g): {"static_params": f"{{'max_depth': {d}}}"}
                  for g, d in enumerate((6, 8, 10))},
    "geometry": {"groups": [{"group": g, "n_candidates": 3}
                            for g in range(3)]},
}


def reader(name):
    return run.load_file(os.path.join(BENCH, "layers", name + ".py")).read


def ctx_with(report, reduced=None, trace=None):
    return {"config": CONFIG, "report": report, "reports": [report],
            "n_candidates": 9, "fits_per_search": 45, "chips": 1,
            "device": {"platform": "tpu", "kind": "TPU v5 lite"},
            "trace": trace, "work": work, "cell": {"name": "a.cell"},
            "load_named": lambda spec: (
                (lambda ctx: reduced) if spec == "scopes:read"
                else run.load_named(spec, BENCH))}


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
    assert "no sst.tree.* scope" in capsys.readouterr().out


@pytest.mark.parametrize("name", DEVICE)
def test_no_trace_reads_as_nothing(name):
    assert reader(name)(ctx_with(REPORT)) is None


def test_device_readers_on_the_growers_scopes(capsys):
    reduced = {"scopes": {
        "sst.tree.bootstrap": 1.0, "sst.tree.partition": 3.0,
        "sst.tree.histogram": 10.0, "sst.tree.split": 4.0,
        "sst.tree.route": 1.5, "sst.tree.predict": 0.5,
        "sst.fit": 2.0, "sst.score": 0.25, "unscoped": 0.125}}
    ctx = ctx_with(REPORT, reduced)
    assert reader("forest.device_s")(ctx) == pytest.approx(20.0)
    assert reader("forest.hist_share")(ctx) == pytest.approx(50.0)
    assert reader("forest.partition_share")(ctx) == pytest.approx(22.5)
    needs = work_forest.forest_histograms(CONFIG, 9, REPORT)
    share = reader("tree_histogram_roofline")(ctx)
    assert share == pytest.approx(
        100.0 * needs["fit_bytes"] / 819e9 / 10.0)
    assert 0.0 < share < 100.0
    assert "bound by bytes" in capsys.readouterr().out


def test_counter_readers():
    ctx = ctx_with(REPORT)
    # 9 candidates' 210 trees x 5 folds of 1 800 lockstep slots
    assert reader("forest.idle_tree_share")(ctx) == pytest.approx(
        100.0 * (1.0 - 1050.0 / 1800.0))
    assert reader("forest.tree_levels")(ctx) == 14400


@pytest.mark.parametrize("name", COUNTED)
def test_a_program_from_before_the_counters_reads_as_nothing(name):
    assert reader(name)(ctx_with({"lanes_per_launch": [15]})) is None


def test_work_model_counts_what_the_trees_need():
    needs = work_forest.forest_histograms(CONFIG, 9, REPORT)
    n_train = 145253 - 145253 // 5
    in_bag = n_train * (1.0 - 2.718281828459045 ** -1)
    levels = 70 * (6 + 8 + 10)             # one fold's tree-levels
    assert work_forest.tree_levels(REPORT, 9) == (
        210, levels, 70 * (63 + 255 + 1023))
    assert needs["fit_flops"] == pytest.approx(5 * levels * in_bag * 8 * 54)
    assert needs["fit_bytes"] == pytest.approx(
        5 * (levels * in_bag * 62 + 70 * 1341 * 8))
    assert needs["flops"] > needs["fit_flops"]


@pytest.mark.parametrize("broken", [
    {"trees_per_candidate": None},
    {"trees_per_candidate": [10, 20, 40] * 2},
    {"trees_per_candidate": [-1] * 9},
    {"per_group": {}},
    {"geometry": {"groups": [{"group": 0, "n_candidates": 3}]}},
])
def test_work_model_makes_no_share_of_a_guess(broken):
    assert work_forest.forest_histograms(
        CONFIG, 9, {**REPORT, **broken}) is None
