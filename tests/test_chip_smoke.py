"""chip_smoke.py — the chip proof's phase functions at toy shapes.

The script itself only passes on the TPU (it has no switch that says
otherwise); what tier-1 can pin on the CPU mesh is that its phases run
end to end through the public entry points, that its checks bite, and
that the script refuses to pass where jax finds no accelerator.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402


@pytest.fixture(scope="module")
def clock():
    return cs.CompileClock()


def test_exits_nonzero_and_prints_no_result_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode not in (0, None), proc.stdout[-500:]
    assert "not 'tpu'" in proc.stderr
    assert '"ok"' not in proc.stdout and proc.stdout.strip() == ""


def test_verdict_line_has_exactly_the_contract_keys():
    # the driver refuses a last line wider than this; the measurements
    # live on the `summary:` line before it
    line = json.loads(json.dumps(cs.verdict_line(
        True, {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})))
    assert line == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert cs.verdict_line(False, cs.device_block())["ok"] is False


def test_report_ends_stdout_with_the_verdict(tmp_path, monkeypatch, capsys):
    monkeypatch.chdir(tmp_path)
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}
    for ok in (True, False):
        result = {"ok": ok, "device": dev, "warm": {"wall_s": 1.0},
                  "failures": [] if ok else ["oracle: off"]}
        assert cs.report(result) == (0 if ok else 1)
        lines = capsys.readouterr().out.splitlines()
        assert json.loads(lines[-1]) == {"ok": ok, "device": dev}
        assert lines[-2].startswith("summary: ")
        assert json.loads(lines[-2][len("summary: "):]) == result
        with open(tmp_path / "chiprun_out" / "chip_smoke_result.json") as fh:
            assert json.load(fh) == result


def test_every_registered_class_has_a_toy_grid():
    from spark_sklearn_tpu.models.base import _FAMILIES_BY_CLASSNAME

    registered = cs.registered_classes()
    assert {cls.__name__ for cls, _ in registered} == set(cs._TOY_GRIDS)
    # aliases collapse onto classes; no family is lost on the way
    assert {_FAMILIES_BY_CLASSNAME[qn] for _, qn in registered} \
        == set(_FAMILIES_BY_CLASSNAME.values())


def test_main_path_oracle_and_checks_at_toy_shapes(clock, digits):
    import jax

    # eight candidates for the timed pair; the public call and its
    # oracle at the chip run's own shape (ten C, five folds — 50 fits)
    main = cs.phase_main_path(clock, n_candidates=8, data=digits)
    assert main["fits"] == 40
    assert main["warm"]["n_compiles"] == 0
    assert main["cold"]["n_compiles"] > 0
    assert main["cold_warm_equal"]
    assert main["mesh"] == {"task": len(jax.devices()), "data": 1}
    assert main["public"]["refit_train_accuracy"] > 0.9
    oracle = cs.phase_oracle(main, data=digits)
    # the gate: every one of the ten C within 5e-3 of sklearn with both
    # solvers converged; at the default tol the stable facts only
    assert len(oracle["ours"]) == 10 and oracle["tol"] == cs.ORACLE_TOL
    assert oracle["max_abs_diff"] <= cs.ORACLE_ATOL == 5e-3
    dflt = oracle["default_tol"]
    assert len(dflt["ours"]) == 10
    assert dflt["best_C"] == dflt["sklearn_best_C"]
    # the several-chip phase, on the 8 virtual devices: bit-equal split
    # scores at equal lanes per device
    parity = cs.phase_one_device_parity(main, n_candidates=8, data=digits)
    assert parity["all_device_mesh"]["task"] == len(jax.devices())
    assert parity["one_device_mesh"] == {"task": 1, "data": 1}
    assert parity["lanes_per_device"] == 5
    assert parity["equal"] and parity["n_split_scores_unequal"] == 0
    # XLA:CPU has no allocator stats, so the device-memory check — part
    # of every chip run — can never hold here: the smoke cannot pass
    # on this backend even past its platform gate
    with pytest.raises(cs.SmokeFailure, match="measured"):
        cs.check_device_memory(main["warm_report"], "cpu")
    # the checks bite
    bad = dict(main["warm_report"])
    bad["faults"] = dict(bad["faults"], host_fallbacks=1)
    with pytest.raises(cs.SmokeFailure, match="host_fallbacks"):
        cs.check_clean_faults(bad, "seeded")
    with pytest.raises(cs.SmokeFailure, match="does not span"):
        cs.check_mesh(main["warm_report"], len(jax.devices()) + 1, "seeded")


def test_census_rows_at_toy_shapes(clock, monkeypatch):
    table, n_failed = cs.phase_census(
        clock, n_keys=8,
        only=["sklearn:GaussianNB", "mode:scan+heartbeat",
              "mode:keyed-linear-regression"])
    assert n_failed == 0, table
    assert [r["case"] for r in table] == [
        "sklearn:GaussianNB", "mode:scan+heartbeat",
        "mode:keyed-linear-regression"]
    assert all(r["ok"] and "compile_s" in r for r in table)
    json.dumps(table, default=cs._json_default)
    # a failing case is recorded with its error, and the rest still run
    monkeypatch.setitem(cs._TOY_GRIDS, "GaussianNB",
                        ({}, {"var_smoothing": ["junk", -1.0]}, "cls"))
    table, n_failed = cs.phase_census(
        clock, only=["sklearn:GaussianNB", "sklearn:MultinomialNB"])
    assert n_failed == 1
    assert [r["ok"] for r in table] == [False, True]
    assert table[0]["error"]
