"""The seven ``setup.*`` / ``build.*`` readers of ISSUE 37: on a hand-made
``search_report["process"]`` block, on a report without the block (the
parent's: ``None``, nothing raised), and under the runner end to end on
XLA:CPU at the tiny size (``rehearse.py``, a process of its own), where the
``setup:`` line's parts must add up to the traced search's start."""

import json
import os
import subprocess
import sys

import pytest

import run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
LAYERS = os.path.join(os.path.dirname(HERE), "layers")

NAMES = ("setup.import_s", "setup.before_first_call_s", "setup.first_fit_s",
         "build.programs", "build.cache_load_s", "build.trace_lower_s",
         "build.blocked_s")


def reader(name):
    return run.load_file(os.path.join(LAYERS, name + ".py"))


def block():
    build = {"name": "jit(fused)", "label": "fused group 0",
             "thread": "sst-compile_0", "search": 1, "t0_s": 17.0,
             "t1_s": 21.5, "trace_s": 0.4, "lower_s": 0.1,
             "cache_load_s": 3.9, "xla_s": 0.1, "cache": "hit",
             "blocking": False}
    return {
        "import_s": 9.0, "import_own_s": 0.3,
        "import_by_root": {"numpy": 0.5, "jax": 3.0, "scipy": 3.2,
                           "sklearn": 2.0},
        "first_call_s": 15.5,
        "fits": [{"search": 1, "t0_s": 16.75, "t1_s": 28.0},
                 {"search": 2, "t0_s": 28.25, "t1_s": 36.0}],
        "n_programs": 3, "n_cache_hits": 3, "n_cache_misses": 0,
        "trace_s": 1.0, "lower_s": 0.5, "xla_s": 0.25, "cache_load_s": 4.5,
        "build_union_s": 5.0, "build_blocked_s": 2.75,
        "builds": [build],
    }


def test_readers_on_a_hand_made_block(capsys):
    ctx = {"report": {"process": block()}}
    got = {name: reader(name).read(ctx) for name in NAMES}
    assert got == {
        "setup.import_s": 9.0, "setup.before_first_call_s": 6.5,
        "setup.first_fit_s": 11.25, "build.programs": 3,
        "build.cache_load_s": 4.5, "build.trace_lower_s": 1.5,
        "build.blocked_s": 2.75}
    line = [l for l in capsys.readouterr().out.splitlines()
            if l.startswith("setup: ")]
    assert len(line) == 1                 # build.programs prints it, once
    said = json.loads(line[0][len("setup: "):])
    assert list(said["parts_s"]) == [
        "import", "before_first_call", "first_call_to_first_fit",
        "first_fit", "first_fit_to_traced_search"]
    assert said["parts_s"] == {
        "import": 9.0, "before_first_call": 6.5,
        "first_call_to_first_fit": 1.25, "first_fit": 11.25,
        "first_fit_to_traced_search": 0.25}
    assert said["sum_s"] == said["traced_search_t0_s"] == 28.25
    assert said["builds"] == [
        [17.0, "fused group 0", "sst-compile_0", 4.5, "hit", 0]]


@pytest.mark.parametrize("name", NAMES)
def test_reader_on_a_report_without_the_block(name, capsys):
    assert reader(name).read({"report": {"backend": "tpu"}}) is None
    assert "setup:" not in capsys.readouterr().out


@pytest.mark.parametrize("name", ["setup.before_first_call_s",
                                  "setup.first_fit_s", "build.programs"])
def test_reader_before_the_first_call_or_fit(name, capsys):
    early = dict(block(), first_call_s=None, fits=[])
    value = reader(name).read({"report": {"process": early}})
    assert value == (3 if name == "build.programs" else None)
    assert "setup:" not in capsys.readouterr().out


def test_entries_are_appended_and_move_setup():
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    tail = bench["per_layer"][-len(NAMES):]
    assert [m["name"] for m in tail] == list(NAMES)
    for m in tail:
        assert m["moves"] == "setup_s" and m["better"] == "lower"
        assert "workloads" not in m
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves"}


def test_rehearsal_prints_the_seven_and_the_parts_add_up():
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse.py"), "--chips", "1",
         "--seconds", "0.5", "--trace", "1"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env={**os.environ})
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True
    metrics = result["metrics"]
    assert set(NAMES) <= set(metrics)
    said = json.loads(next(
        l for l in lines if l.startswith("setup: "))[len("setup: "):])
    parts = said["parts_s"]
    assert abs(sum(parts.values()) - said["traced_search_t0_s"]) < 0.05
    assert all(v >= 0.0 for v in parts.values())
    assert parts["import"] == pytest.approx(
        metrics["setup.import_s"]["value"], abs=1e-3)
    assert parts["first_fit"] == pytest.approx(
        metrics["setup.first_fit_s"]["value"], abs=1e-3)
    # the import's parts are the import
    assert sum(said["import_by_root_s"].values()) + said["import_own_s"] \
        == pytest.approx(parts["import"], abs=1e-2)
    # the warm-up built the programs, the traced search none; each of
    # them once, so no more seconds than the runner's own doubled sum
    assert metrics["build.programs"]["value"] >= 2
    assert metrics["build.window_compiles"]["value"] == 0
    built = (metrics["build.trace_lower_s"]["value"]
             + metrics["build.cache_load_s"]["value"]
             + said["totals"]["xla_s"])
    assert 0.0 < built <= metrics["build.compile_s"]["value"] + 0.05
    assert metrics["build.blocked_s"]["value"] \
        <= metrics["setup.first_fit_s"]["value"]
    assert len(said["builds"]) >= 2
