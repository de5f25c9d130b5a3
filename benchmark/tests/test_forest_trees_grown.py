"""``forest.trees_grown`` on ``search_report``s with and without the
counter it reads, and the forest cell's other counted readers on the
report of a search whose launches share their trees (one forest a fold,
read at every ``n_estimators`` of the launch)."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

import run              # noqa: E402

# the cell's search, three depths x three counts x five folds: as a program
# that grows every candidate's own trees reports it, and as one that grows
# a fold's forest once a depth
OWN_TREES = {
    "trees_per_candidate": [10, 20, 40] * 3,
    "tree_slots_per_launch": [600, 600, 600],
    "tree_levels_per_launch": [3600, 4800, 6000],
}
SHARED_TREES = {
    "trees_per_candidate": [10, 20, 40] * 3,
    "trees_grown_per_launch": [200, 200, 200],
    "tree_slots_per_launch": [200, 200, 200],
    "tree_levels_per_launch": [1200, 1600, 2000],
}


def reader(name):
    return run.load_file(os.path.join(BENCH, "layers", name + ".py")).read


def ctx_with(report):
    return {"report": report, "reports": [report], "n_candidates": 9,
            "fits_per_search": 45, "chips": 1}


@pytest.mark.parametrize("report,grown", [
    (SHARED_TREES, 600),            # the counter
    (OWN_TREES, 1050),              # no counter: 210 trees x 5 folds
    ({**SHARED_TREES, "trees_grown_per_launch": [200, 120, 80, 200]}, 600),
    ({**OWN_TREES, "trees_grown_per_launch": []}, 1050),
])
def test_trees_grown(report, grown):
    assert reader("forest.trees_grown")(ctx_with(report)) == grown


@pytest.mark.parametrize("report", [
    {"lanes_per_launch": [15]},                     # another family
    {"trees_per_candidate": []},
    {"trees_per_candidate": [10, -1, 40]},          # restored candidates
])
def test_trees_grown_reads_nothing_without_counters(report):
    assert reader("forest.trees_grown")(ctx_with(report)) is None


def test_counted_readers_on_shared_trees():
    """A grown tree serves 1.75 candidates: the share of slots spent on
    lanes already done, counted against each candidate's own trees, goes
    below zero and is still a number."""
    ctx = ctx_with(SHARED_TREES)
    assert reader("forest.idle_tree_share")(ctx) == pytest.approx(-75.0)
    assert reader("forest.tree_levels")(ctx) == 4800


def test_trees_grown_is_declared_for_the_forest_cell():
    bench = run.load_json(os.path.join(os.path.dirname(BENCH),
                                       "BENCHMARK.json"))
    entry = bench["per_layer"][-1]
    assert entry == {
        "name": "forest.trees_grown", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "solvers",
        "moves": "search_wall_s",
        "workloads": ["forest_covtype145k.depth3_trees3"]}
