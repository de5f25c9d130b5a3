"""``boost.lanes_per_mask`` on ``search_report``s with and without the
``hist_lanes_per_mask`` row it reads, and its entry in the benchmark."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

import run              # noqa: E402

CELL = "gbc_covtype145k.lr5_stages3"


def read(report):
    reader = run.load_file(os.path.join(BENCH, "layers",
                                        "boost.lanes_per_mask.py")).read
    return reader({"report": report, "reports": [report],
                   "n_candidates": 15, "fits_per_search": 75, "chips": 1})


@pytest.mark.parametrize("lanes,want", [
    ([5], 5),               # the cell: a fold's 5 runs share each mask
    ([5, 5], 5),
    ([5, 1], 1),            # the fewest over the launches
    ([1], 1),               # every lane its own tiles
    ([0], 0),               # the plain form builds no mask
])
def test_reads_the_fewest_lanes_a_mask_serves(lanes, want):
    assert read({"tree_steps_per_launch": [2500],
                 "hist_lanes_per_mask": lanes}) == want


@pytest.mark.parametrize("report", [
    # the parent: the boosting counters, no row of masks
    {"tree_steps_per_launch": [2500], "row_sorts_per_launch": [25]},
    {"hist_lanes_per_mask": []},
    {"lanes_per_launch": [190] * 8},            # another family
])
def test_a_report_without_the_row_reads_as_nothing(report):
    assert read(report) is None


def test_is_declared_for_the_boosting_cell():
    bench = run.load_json(os.path.join(os.path.dirname(BENCH),
                                       "BENCHMARK.json"))
    entries = [m for m in bench["per_layer"]
               if m["name"] == "boost.lanes_per_mask"]
    assert entries == [{
        "name": "boost.lanes_per_mask", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "solvers",
        "moves": "search_wall_s", "workloads": [CELL]}]
