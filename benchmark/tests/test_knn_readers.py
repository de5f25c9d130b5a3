"""The k-NN cell's per-layer readers on made-up reductions (no trace is
needed to check arithmetic): what each reads, and that each is silent on a
search without the neighbour search's scopes or counters."""

import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)

import run          # noqa: E402  (conftest puts the benchmark on the path)
import work         # noqa: E402


def ctx_with(scopes, report=None):
    with open(os.path.join(BENCH, "configs", "knn_mnist.json")) as f:
        config = json.load(f)
    reduced = {"scopes": scopes}
    return {
        "config": config, "n_candidates": 60, "chips": 1,
        "device": {"kind": "TPU v5 lite", "platform": "tpu"},
        "report": report or {}, "trace": {"busy_s": 1.0}, "work": work,
        "load_named": lambda spec: (
            (lambda ctx: reduced) if spec == "scopes:read"
            else run.load_named(spec, BENCH)),
    }


def reader(name):
    return run.load_file(os.path.join(BENCH, "layers", name + ".py")).read


SCOPES = {"sst.knn.distance": 0.3, "sst.knn.select": 0.6,
          "sst.knn.vote": 0.1, "sst.score": 0.2, "sst.fit": 0.05}


def test_device_seconds_and_select_share():
    ctx = ctx_with(SCOPES)
    assert reader("knn.device_s")(ctx) == pytest.approx(1.0)
    assert reader("knn.select_share")(ctx) == pytest.approx(60.0)


def test_roofline_is_one_pass_of_the_required_product():
    """6.147e12 operations at 197 TFLOP/s: 31.2 ms, over the 0.9 s under
    distance and selection."""
    ctx = ctx_with(SCOPES)
    share = reader("knn_neighbors_roofline")(ctx)
    needs = run.load_named("work_knn:knn_neighbors", BENCH)(
        ctx["config"], 60)
    assert needs["fit_flops"] == pytest.approx(2 * 70000 * 56000 * 784)
    assert share == pytest.approx(100 * needs["fit_flops"] / 197e12 / 0.9,
                                  rel=1e-3)


def test_distance_entries_sum_the_launches():
    ctx = ctx_with(SCOPES, {"knn_distance_entries_per_launch":
                            [70000 * 70000]})
    assert reader("knn.distance_entries")(ctx) == 4.9e9


@pytest.mark.parametrize("name", ["knn.device_s", "knn.select_share",
                                  "knn_neighbors_roofline",
                                  "knn.distance_entries"])
def test_silent_without_the_neighbour_search(name):
    ctx = ctx_with({"sst.boost.gradient": 1.0, "sst.score": 0.2})
    assert reader(name)(ctx) is None


def test_work_is_none_for_another_grid():
    config = ctx_with({})["config"]
    assert run.load_named("work_knn:knn_neighbors", BENCH)(config, 59) \
        is None
