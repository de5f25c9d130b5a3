"""``forest.hist_feature_share`` on ``search_report``s with and without the
fact it reads: a program that builds each node's own features, one that
builds every feature, a booster's, and another family's."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

import run              # noqa: E402

NAME = "forest.hist_feature_share"
CELL = "forest_covtype145k.depth3_trees3"


def groups(*pairs):
    return {"per_group": {
        str(g): {"static_params": "{}", "n_launches": 2, **(
            {"hist_features": held, "n_features": d} if held else
            {"n_features": d} if d else {})}
        for g, (held, d) in enumerate(pairs)}}


def read(report):
    reader = run.load_file(os.path.join(BENCH, "layers", NAME + ".py")).read
    return reader({"report": report, "reports": [report], "n_candidates": 9,
                   "fits_per_search": 45, "chips": 1})


@pytest.mark.parametrize("report,share", [
    # the cell's three depths, a node's own 7 of covtype's 54
    ({**groups((7, 54), (7, 54), (7, 54)),
      "hist_bytes_per_lane": [2 ** 20] * 3}, 100.0 * 7 / 54),
    (groups((54, 54)), 100.0),                  # a booster: every feature
    (groups((7, 54), (54, 54)), 100.0 * 61 / 108),
    # a forest's report from before the fact: every feature, masked after
    ({**groups((None, None)), "hist_bytes_per_lane": [268435456] * 3},
     100.0),
    ({**groups((None, 54)), "hist_bytes_per_lane": [268435456]}, 100.0),
])
def test_share(report, share):
    assert read(report) == pytest.approx(share)
    if share < 100:
        assert round(read(groups((7, 54))), 2) == 12.96


@pytest.mark.parametrize("report", [
    {},                                         # no per_group at all
    {"per_group": None, "lanes_per_launch": [15]},
    groups((None, 784)),                        # another family's groups
    {**groups((None, None)), "hist_bytes_per_lane": []},
])
def test_reads_nothing_of_another_familys_search(report):
    assert read(report) is None


def test_declared_for_the_forest_cell():
    bench = run.load_json(os.path.join(os.path.dirname(BENCH),
                                       "BENCHMARK.json"))
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "solvers",
        "moves": "search_wall_s", "workloads": [CELL]}
    assert NAME in [m["name"] for m in run.metrics_of(bench, CELL,
                                                      "per_layer")]
    assert os.path.exists(os.path.join(BENCH, "layers", NAME + ".py"))
