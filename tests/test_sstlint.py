"""sstlint's own suite: fixture trees per rule (positive + negative +
suppression), baseline round-trip, the runtime lock-order recorder,
and the real-tree gate (the package must lint clean)."""

import json
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from tools.sstlint import Project, run_lint, save_baseline  # noqa: E402
from tools.sstlint.core import load_baseline  # noqa: E402


def make_project(root: Path, **kw) -> Project:
    pkg = root / "pkg"
    pkg.mkdir(parents=True, exist_ok=True)
    defaults = dict(root=root, package=pkg)
    defaults.update(kw)
    return Project(**defaults)


def write(root: Path, rel: str, text: str) -> Path:
    p = root / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(text)
    return p


def lint(project, rules):
    return run_lint(project, rules=rules,
                    baseline_path=project.root / "baseline.json")


def rule_hits(result, rule):
    return [f for f in result["findings"] if f["rule"] == rule]


# ---------------------------------------------------------------------------
# exception hygiene
# ---------------------------------------------------------------------------


class TestExceptRules:
    def test_bare_except_flagged_and_suppressed(self, tmp_path):
        write(tmp_path, "pkg/a.py", (
            "def f():\n"
            "    try:\n"
            "        work()\n"
            "    except:\n"
            "        return None\n"
            "def g():\n"
            "    try:\n"
            "        work()\n"
            "    # justified: legacy shim\n"
            "    # sstlint: disable=bare-except\n"
            "    except:\n"
            "        return None\n"
            "def h():\n"
            "    try:\n"
            "        work()\n"
            "    except ValueError:\n"
            "        return None\n"))
        r = lint(make_project(tmp_path), ["bare-except"])
        hits = rule_hits(r, "bare-except")
        assert len(hits) == 1 and hits[0]["line"] == 4

    def test_broad_baseexception_requires_reraise(self, tmp_path):
        write(tmp_path, "pkg/a.py", (
            "def bad():\n"
            "    try:\n"
            "        work()\n"
            "    except BaseException as exc:\n"
            "        log(exc)\n"
            "def ok():\n"
            "    try:\n"
            "        work()\n"
            "    except BaseException:\n"
            "        raise\n"))
        r = lint(make_project(tmp_path), ["broad-except-swallow"])
        hits = rule_hits(r, "broad-except-swallow")
        assert len(hits) == 1 and hits[0]["line"] == 4

    def test_swallowed_exception(self, tmp_path):
        write(tmp_path, "pkg/a.py", (
            "import warnings\n"
            "def bad():\n"
            "    try:\n"
            "        work()\n"
            "    except Exception:\n"
            "        pass\n"
            "def ok_logs():\n"
            "    try:\n"
            "        work()\n"
            "    except Exception as exc:\n"
            "        warnings.warn(f'fallback: {exc}')\n"))
        r = lint(make_project(tmp_path), ["swallowed-exception"])
        hits = rule_hits(r, "swallowed-exception")
        assert len(hits) == 1 and hits[0]["line"] == 5

    def test_raise_without_cause(self, tmp_path):
        write(tmp_path, "pkg/a.py", (
            "def bad():\n"
            "    try:\n"
            "        work()\n"
            "    except ValueError as exc:\n"
            "        raise RuntimeError('translated')\n"
            "def ok():\n"
            "    try:\n"
            "        work()\n"
            "    except ValueError as exc:\n"
            "        raise RuntimeError('translated') from exc\n"))
        r = lint(make_project(tmp_path), ["raise-without-cause"])
        hits = rule_hits(r, "raise-without-cause")
        assert len(hits) == 1 and hits[0]["line"] == 5

    def test_launch_taxonomy(self, tmp_path):
        write(tmp_path, "pkg/launchy.py", (
            "def classify_error(e):\n"
            "    return 'fatal'\n"
            "def bad_handler():\n"
            "    try:\n"
            "        launch()\n"
            "    except Exception as exc:\n"
            "        return None\n"
            "def ok_handler():\n"
            "    try:\n"
            "        launch()\n"
            "    except Exception as exc:\n"
            "        if classify_error(exc) == 'fatal':\n"
            "            raise\n"))
        proj = make_project(tmp_path, launch_paths=("launchy.py",))
        r = lint(proj, ["launch-except-taxonomy"])
        hits = rule_hits(r, "launch-except-taxonomy")
        assert len(hits) == 1 and hits[0]["line"] == 6


# ---------------------------------------------------------------------------
# lock order / shared state
# ---------------------------------------------------------------------------


class TestLockRules:
    def test_lock_order_cycle(self, tmp_path):
        write(tmp_path, "pkg/locksmod.py", (
            "A = named_lock('m.A')\n"
            "B = named_lock('m.B')\n"
            "def one():\n"
            "    with A:\n"
            "        with B:\n"
            "            pass\n"
            "def two():\n"
            "    with B:\n"
            "        with A:\n"
            "            pass\n"))
        r = lint(make_project(tmp_path), ["lock-order-cycle"])
        assert rule_hits(r, "lock-order-cycle")

    def test_consistent_order_clean(self, tmp_path):
        write(tmp_path, "pkg/locksmod.py", (
            "A = named_lock('m.A')\n"
            "B = named_lock('m.B')\n"
            "def one():\n"
            "    with A:\n"
            "        with B:\n"
            "            pass\n"
            "def two():\n"
            "    with A:\n"
            "        with B:\n"
            "            pass\n"))
        r = lint(make_project(tmp_path), ["lock-order-cycle"])
        assert not rule_hits(r, "lock-order-cycle")

    def test_deferred_callback_is_not_under_the_lock(self, tmp_path):
        # a callback DEFINED under lock A runs in whatever frame later
        # invokes it: acquiring B in its body is no A->B edge, and a
        # shared-state mutation in its body is NOT guarded by A
        from tools.sstlint.project import SharedState
        write(tmp_path, "pkg/locksmod.py", (
            "A = named_lock('m.A')\n"
            "B = named_lock('m.B')\n"
            "TOTALS = {'n': 0}\n"
            "def install():\n"
            "    with A:\n"
            "        def cb():\n"
            "            with B:\n"
            "                pass\n"
            "        register(cb)\n"
            "def other():\n"
            "    with B:\n"
            "        with A:\n"
            "            pass\n"
            "def install2():\n"
            "    with A:\n"
            "        def cb2():\n"
            "            TOTALS['n'] += 1\n"
            "        register(cb2)\n"))
        proj = make_project(tmp_path, shared_state=(
            SharedState("locksmod.py", "m.A", name="TOTALS"),))
        r = lint(proj, ["lock-order-cycle", "unlocked-shared-mutation"])
        # no false A->B edge from cb, so B->A in other() is no cycle
        assert not rule_hits(r, "lock-order-cycle")
        # and cb2's mutation is correctly seen as unguarded
        assert [f["line"] for f in
                rule_hits(r, "unlocked-shared-mutation")] == [17]

    def test_cross_module_lock_including_call_through(self, tmp_path):
        # nested with across module prefixes, via a one-hop call
        write(tmp_path, "pkg/other.py", (
            "L2 = named_lock('other.L2')\n"
            "def locked_op():\n"
            "    with L2:\n"
            "        pass\n"))
        write(tmp_path, "pkg/main.py", (
            "from pkg.other import locked_op\n"
            "L1 = named_lock('main.L1')\n"
            "def f():\n"
            "    with L1:\n"
            "        locked_op()\n"))
        proj = make_project(tmp_path)
        r = lint(proj, ["cross-module-lock"])
        hits = rule_hits(r, "cross-module-lock")
        assert len(hits) == 1
        assert "other.L2" in hits[0]["message"]
        # the allowlist silences the pair
        proj2 = make_project(tmp_path,
                             allowed_cross_module=(("main", "other"),))
        r2 = lint(proj2, ["cross-module-lock"])
        assert not rule_hits(r2, "cross-module-lock")

    def test_unlocked_shared_mutation(self, tmp_path):
        from tools.sstlint.project import SharedState
        write(tmp_path, "pkg/state.py", (
            "TOTALS = {'bytes': 0}\n"
            "LOCK = named_lock('state.LOCK')\n"
            "def bad(n):\n"
            "    TOTALS['bytes'] += n\n"
            "def good(n):\n"
            "    with LOCK:\n"
            "        TOTALS['bytes'] += n\n"
            "def bad_taint(plan, cid):\n"
            "    done = plan.setdefault('staged_ids', set())\n"
            "    done.add(cid)\n"
            "def good_taint(plan, cid):\n"
            "    done = plan.setdefault('staged_ids', set())\n"
            "    with LOCK:\n"
            "        done.add(cid)\n"))
        proj = make_project(tmp_path, shared_state=(
            SharedState("state.py", "state.LOCK", name="TOTALS"),
            SharedState("state.py", "state.LOCK",
                        taint_key="staged_ids"),
        ))
        r = lint(proj, ["unlocked-shared-mutation"])
        lines = sorted(f["line"] for f in
                       rule_hits(r, "unlocked-shared-mutation"))
        assert lines == [4, 10]

    def test_unnamed_lock(self, tmp_path):
        write(tmp_path, "pkg/a.py", (
            "import threading\n"
            "GOOD = named_lock('a.GOOD')\n"
            "BAD = threading.Lock()\n"))
        r = lint(make_project(tmp_path), ["unnamed-lock"])
        hits = rule_hits(r, "unnamed-lock")
        assert len(hits) == 1 and hits[0]["line"] == 3


# ---------------------------------------------------------------------------
# spans + schema + docs
# ---------------------------------------------------------------------------

_FIXTURE_SPANS = (
    "KNOWN = {'stage', 'dispatch'}\n"
    "ASYNC = ('launch',)\n"
    "def known_span_names():\n"
    "    return frozenset(KNOWN)\n"
    "def async_prefix(name):\n"
    "    for p in ASYNC:\n"
    "        if name == p or name.startswith(p + ' '):\n"
    "            return p\n"
    "    return None\n"
    "def is_known_span(name):\n"
    "    return name in KNOWN or async_prefix(name) is not None\n")


class TestSpanRules:
    def test_span_vocabulary(self, tmp_path):
        spans = write(tmp_path, "pkg/spans.py", _FIXTURE_SPANS)
        write(tmp_path, "pkg/a.py", (
            "def f(tracer, key):\n"
            "    with tracer.span('stage', key=key):\n"
            "        pass\n"
            "    with tracer.span('stag', key=key):\n"
            "        pass\n"
            "    tracer.record_async(f'launch {key}', 0, 1, track='t')\n"
            "    tracer.record_async(f'lunch {key}', 0, 1, track='t')\n"))
        proj = make_project(tmp_path, spans_path=spans)
        r = lint(proj, ["span-unknown-name"])
        syms = sorted(f["message"] for f in
                      rule_hits(r, "span-unknown-name"))
        assert len(syms) == 2
        assert any("'stag'" in s for s in syms)
        assert any("'lunch'" in s for s in syms)

    @pytest.mark.parametrize("call, flagged", [
        ("jax.named_scope('glm_lbfgs.forward')", False),
        ("named_scope('sst.fit')", False),
        ("jax.named_scope('glm_lbfgs.forwrd')", True),
        ("jax.named_scope('stage')", True),      # a span is no scope
        ("jax.named_scope(name)", True),         # not checkable
    ])
    def test_named_scope_vocabulary(self, tmp_path, call, flagged):
        spans = write(tmp_path, "pkg/spans.py", _FIXTURE_SPANS + (
            "def known_scope_names():\n"
            "    return frozenset({'glm_lbfgs.forward', 'sst.fit'})\n"))
        write(tmp_path, "pkg/a.py", (
            "def f(jax, named_scope, name):\n"
            f"    with {call}:\n"
            "        pass\n"))
        proj = make_project(tmp_path, spans_path=spans)
        hits = rule_hits(lint(proj, ["span-unknown-name"]),
                         "span-unknown-name")
        assert len(hits) == (1 if flagged else 0), hits
        if flagged:
            assert hits[0]["line"] == 2

    def test_span_context_manager(self, tmp_path):
        spans = write(tmp_path, "pkg/spans.py", _FIXTURE_SPANS)
        write(tmp_path, "pkg/a.py", (
            "def f(tracer):\n"
            "    s = tracer.span('stage')\n"
            "    s.__enter__()\n"
            "def g(tracer):\n"
            "    with tracer.span('stage'):\n"
            "        pass\n"))
        proj = make_project(tmp_path, spans_path=spans)
        r = lint(proj, ["span-not-context-managed"])
        hits = rule_hits(r, "span-not-context-managed")
        assert len(hits) == 1 and hits[0]["line"] == 2

    def test_schema_block_drift_both_directions(self, tmp_path):
        # schema misses a produced key ('extra') AND declares one
        # nothing produces ('missing') — the ISSUE's drift fixture
        metrics = write(tmp_path, "pkg/metrics.py", (
            "from collections import namedtuple\n"
            "MetricDef = namedtuple('MetricDef', 'name kind')\n"
            "DATAPLANE_BLOCK_SCHEMA = (\n"
            "    MetricDef('hits', 'counter'),\n"
            "    MetricDef('missing', 'gauge'),\n"
            ")\n"))
        write(tmp_path, "pkg/plane.py", (
            "def report_block(plane):\n"
            "    return {'hits': plane.hits, 'extra': 1}\n"))
        from tools.sstlint.project import BlockSpec, Producer
        proj = make_project(
            tmp_path, metrics_path=metrics,
            blocks=(BlockSpec("dataplane", "DATAPLANE_BLOCK_SCHEMA", (
                Producer("dict-keys", "plane.py", "report_block"),)),))
        r = lint(proj, ["schema-block-drift"])
        msgs = " | ".join(f["message"] for f in
                          rule_hits(r, "schema-block-drift"))
        assert "'extra'" in msgs and "'missing'" in msgs
        assert len(rule_hits(r, "schema-block-drift")) == 2

    def test_report_key_undeclared(self, tmp_path):
        metrics = write(tmp_path, "pkg/metrics.py", (
            "from collections import namedtuple\n"
            "MetricDef = namedtuple('MetricDef', 'name kind')\n"
            "SEARCH_REPORT_SCHEMA = (MetricDef('n_launches', "
            "'counter'),)\n"))
        write(tmp_path, "pkg/engine.py", (
            "def run(metrics):\n"
            "    metrics.counter('n_launches').inc()\n"
            "    metrics.counter('nope').inc()\n"))
        proj = make_project(tmp_path, metrics_path=metrics)
        r = lint(proj, ["report-key-undeclared"])
        hits = rule_hits(r, "report-key-undeclared")
        assert len(hits) == 1 and "'nope'" in hits[0]["message"]

    def test_docs_stale(self, tmp_path):
        from tools.sstlint import catalog_markdown
        metrics = write(tmp_path, "pkg/metrics.py", (
            "def schema_markdown():\n"
            "    return '## schema\\n| a | b |\\n'\n"))
        spans = write(tmp_path, "pkg/spans.py", (
            "def vocabulary_markdown():\n"
            "    return '## spans\\n| s |\\n'\n"))
        docs = write(tmp_path, "docs/API.md", "# API\nstale text\n")
        proj = make_project(tmp_path, metrics_path=metrics,
                            spans_path=spans, docs_api=docs)
        r = lint(proj, ["docs-stale"])
        # one finding per drifted generated section
        assert sorted(f["key"].rsplit("::", 1)[-1]
                      for f in rule_hits(r, "docs-stale")) == [
            "catalog-section", "schema-section", "spans-section"]
        docs.write_text("# API\n## schema\n| a | b |\nmore\n"
                        "## spans\n| s |\n" + catalog_markdown())
        r2 = lint(proj, ["docs-stale"])
        assert not rule_hits(r2, "docs-stale")


# ---------------------------------------------------------------------------
# config knobs
# ---------------------------------------------------------------------------

_FIXTURE_CONFIG = (
    "import dataclasses\n"
    "@dataclasses.dataclass\n"
    "class TpuConfig:\n"
    "    used_knob: int = 1\n"
    "    dead_knob: int = 2\n")


class TestKnobRules:
    def test_config_knob_unread(self, tmp_path):
        write(tmp_path, "pkg/mesh.py", _FIXTURE_CONFIG)
        write(tmp_path, "pkg/engine.py",
              "def f(config):\n    return config.used_knob\n")
        docs = write(tmp_path, "docs/API.md",
                     "used_knob dead_knob\n")
        proj = make_project(tmp_path, docs_api=docs)
        r = lint(proj, ["config-knob-unread"])
        hits = rule_hits(r, "config-knob-unread")
        assert [f["message"] for f in hits] == \
            ["TpuConfig.dead_knob is never read by the package"]

    def test_config_knob_undocumented(self, tmp_path):
        write(tmp_path, "pkg/mesh.py", _FIXTURE_CONFIG)
        write(tmp_path, "pkg/engine.py",
              "def f(c):\n    return c.used_knob + c.dead_knob\n")
        # the match wants the rendered-signature form (`name=` / `name:`)
        # — prose mentioning "dead_knob settings" must NOT count
        docs = write(tmp_path, "docs/API.md",
                     "TpuConfig(used_knob: int = 1)\n"
                     "prose about dead_knob settings\n")
        proj = make_project(tmp_path, docs_api=docs)
        r = lint(proj, ["config-knob-undocumented"])
        hits = rule_hits(r, "config-knob-undocumented")
        assert len(hits) == 1 and "dead_knob" in hits[0]["message"]

    def test_env_knob_unregistered(self, tmp_path):
        write(tmp_path, "pkg/mesh.py", _FIXTURE_CONFIG)
        write(tmp_path, "pkg/engine.py", (
            "import os\n"
            "def f():\n"
            "    a = os.environ.get('SST_USED_KNOB')\n"
            "    b = os.environ.get('SST_ROGUE')\n"
            "    c = os.environ.get('SST_JUSTIFIED')\n"
            "    return a, b, c\n"))
        # knob-table rows: exact | `VAR` | cells (prose doesn't count)
        readme = write(tmp_path, "README.md",
                       "| `SST_USED_KNOB` | x |\n"
                       "| `SST_JUSTIFIED` | y |\n")
        proj = make_project(
            tmp_path, readme=readme,
            env_field_exceptions={"SST_JUSTIFIED": "test harness"})
        r = lint(proj, ["env-knob-unregistered"])
        syms = {f["message"] for f in
                rule_hits(r, "env-knob-unregistered")}
        # SST_ROGUE: no field AND no README row; others clean
        assert len(syms) == 2
        assert all("SST_ROGUE" in m for m in syms)


# ---------------------------------------------------------------------------
# jit purity
# ---------------------------------------------------------------------------


class TestPurityRules:
    def test_impure_sites_flagged(self, tmp_path):
        write(tmp_path, "pkg/progs.py", (
            "import time, random\n"
            "import jax\n"
            "import numpy as np\n"
            "CAPTURED = np.zeros(4)\n"
            "def impure(x):\n"
            "    t = time.perf_counter()\n"
            "    r = random.random()\n"
            "    y = jax.device_put(x)\n"
            "    CAPTURED[0] = 1.0\n"
            "    return x + t + r + y\n"
            "fn = jax.jit(impure)\n"
            "def pure(x):\n"
            "    return x * 2\n"
            "gn = jax.jit(pure)\n"))
        proj = make_project(tmp_path)
        rules = ["jit-impure-time", "jit-impure-random",
                 "jit-unplaned-upload", "jit-host-mutation"]
        r = lint(proj, rules)
        got = {f["rule"] for f in r["findings"]}
        assert got == set(rules)
        # nothing points at the pure function
        assert all("impure" in f["message"] for f in r["findings"])

    def test_vmap_wrapped_and_one_hop(self, tmp_path):
        write(tmp_path, "pkg/progs.py", (
            "import time\n"
            "import jax\n"
            "def helper(x):\n"
            "    return x + time.time()\n"
            "def outer(x):\n"
            "    return helper(x)\n"
            "fn = jax.jit(jax.vmap(outer))\n"))
        r = lint(make_project(tmp_path), ["jit-impure-time"])
        assert rule_hits(r, "jit-impure-time")


# ---------------------------------------------------------------------------
# hygiene + baseline + CLI
# ---------------------------------------------------------------------------


class TestHygieneBaselineCli:
    def test_gitignore_rule(self, tmp_path):
        write(tmp_path, "pkg/a.py", "x = 1\n")
        proj = make_project(tmp_path)
        r = lint(proj, ["gitignore-bytecode"])
        assert rule_hits(r, "gitignore-bytecode")
        write(tmp_path, ".gitignore", "__pycache__/\n*.pyc\n")
        r2 = lint(proj, ["gitignore-bytecode"])
        assert not rule_hits(r2, "gitignore-bytecode")

    def test_baseline_roundtrip(self, tmp_path):
        write(tmp_path, "pkg/a.py", (
            "def f():\n"
            "    try:\n"
            "        work()\n"
            "    except:\n"
            "        return None\n"))
        proj = make_project(tmp_path)
        bl = tmp_path / "baseline.json"
        r = run_lint(proj, rules=["bare-except"], baseline_path=bl)
        assert r["n_findings"] == 1 and r["n_baselined"] == 0
        save_baseline(bl, r["_finding_objs"], r["_baseline"])
        entries = load_baseline(bl)
        assert len(entries) == 1
        r2 = run_lint(proj, rules=["bare-except"], baseline_path=bl)
        assert r2["n_findings"] == 0 and r2["n_baselined"] == 1
        # baselines key on symbols, not line numbers: shifting the
        # function down must not un-baseline the finding
        src = (tmp_path / "pkg/a.py").read_text()
        (tmp_path / "pkg/a.py").write_text("# moved\n\n" + src)
        r3 = run_lint(proj, rules=["bare-except"], baseline_path=bl)
        assert r3["n_findings"] == 0 and r3["n_baselined"] == 1

    def test_cli_real_tree_exits_zero(self):
        out = subprocess.run(
            [sys.executable, "-m", "tools.sstlint", "--format", "json",
             "spark_sklearn_tpu/"],
            capture_output=True, text=True, cwd=str(REPO), timeout=180)
        assert out.returncode == 0, out.stdout + out.stderr
        payload = json.loads(out.stdout)
        assert payload["n_findings"] == 0
        assert payload["n_rules"] >= 20

    def test_cli_seeded_violation_exits_nonzero(self, tmp_path):
        write(tmp_path, "pkg/a.py", (
            "def f():\n"
            "    try:\n"
            "        work()\n"
            "    except:\n"
            "        return None\n"))
        out = subprocess.run(
            [sys.executable, "-m", "tools.sstlint", "--format", "json",
             str(tmp_path / "pkg")],
            capture_output=True, text=True, cwd=str(REPO), timeout=180)
        assert out.returncode == 1
        payload = json.loads(out.stdout)
        assert any(f["rule"] == "bare-except"
                   for f in payload["findings"])

    def test_real_tree_lints_clean_in_process(self):
        r = run_lint(root=REPO)
        assert r["n_findings"] == 0, r["findings"]
        assert r["n_baselined"] == 0, \
            "the committed baseline should stay empty"


# ---------------------------------------------------------------------------
# runtime lock-order recorder (SST_LOCKCHECK)
# ---------------------------------------------------------------------------


class TestLockcheckRuntime:
    def _locks(self):
        from spark_sklearn_tpu.utils.locks import (CheckedLock,
                                                   LockOrderRecorder)
        return CheckedLock, LockOrderRecorder

    def test_inversion_detected(self):
        CheckedLock, LockOrderRecorder = self._locks()
        rec = LockOrderRecorder()
        A = CheckedLock(threading.Lock(), "m.A", rec)
        B = CheckedLock(threading.Lock(), "m.B", rec)

        def ab():
            with A:
                with B:
                    pass

        def ba():
            with B:
                with A:
                    pass

        t1 = threading.Thread(target=ab)
        t1.start()
        t1.join()
        t2 = threading.Thread(target=ba)
        t2.start()
        t2.join()
        rep = rec.report()
        assert rep["n_edges"] == 2
        assert len(rep["inversions"]) == 1
        assert set(rep["inversions"][0]["locks"]) == {"m.A", "m.B"}

    def test_consistent_order_clean(self):
        CheckedLock, LockOrderRecorder = self._locks()
        rec = LockOrderRecorder()
        A = CheckedLock(threading.Lock(), "m.A", rec)
        B = CheckedLock(threading.Lock(), "m.B", rec)
        for _ in range(3):
            with A:
                with B:
                    pass
        rep = rec.report()
        assert rep["edges"] == [("m.A", "m.B")]
        assert not rep["inversions"]

    def test_rlock_reentry_records_no_self_edge(self):
        CheckedLock, LockOrderRecorder = self._locks()
        rec = LockOrderRecorder()
        R = CheckedLock(threading.RLock(), "m.R", rec)
        with R:
            with R:
                pass
        rep = rec.report()
        assert rep["n_edges"] == 0 and not rep["inversions"]

    def test_long_hold_recorded(self, monkeypatch):
        monkeypatch.setenv("SST_LOCKCHECK_HOLD_S", "0.01")
        CheckedLock, LockOrderRecorder = self._locks()
        rec = LockOrderRecorder()
        A = CheckedLock(threading.Lock(), "m.A", rec)
        with A:
            time.sleep(0.05)
        rep = rec.report()
        assert rep["long_holds"] and \
            rep["long_holds"][0]["lock"] == "m.A"

    def test_named_lock_factories_honor_env(self, monkeypatch):
        from spark_sklearn_tpu.utils import locks
        monkeypatch.delenv("SST_LOCKCHECK", raising=False)
        assert not isinstance(locks.named_lock("t.x"),
                              locks.CheckedLock)
        monkeypatch.setenv("SST_LOCKCHECK", "1")
        lk = locks.named_lock("t.x")
        assert isinstance(lk, locks.CheckedLock)
        rk = locks.named_rlock("t.y")
        assert isinstance(rk, locks.CheckedLock)

    def test_engine_search_clean_under_lockcheck(self):
        """End-to-end: a real compiled search in a subprocess with
        SST_LOCKCHECK=1 must record zero inversions (and at least the
        plane->totals edge)."""
        code = (
            "import os\n"
            "import numpy as np\n"
            "import jax\n"
            "jax.config.update('jax_platforms', 'cpu')\n"
            "from sklearn.linear_model import LogisticRegression\n"
            "import spark_sklearn_tpu as sst\n"
            "from spark_sklearn_tpu.utils import locks\n"
            "X = np.random.RandomState(0).randn(64, 4)"
            ".astype(np.float32)\n"
            "y = (X[:, 0] > 0).astype(np.int64)\n"
            "cfg = sst.TpuConfig(fault_plan='transient@1,oom@3',\n"
            "                    retry_backoff_s=0.01)\n"
            "gs = sst.GridSearchCV(LogisticRegression(max_iter=5),\n"
            "    {'C': [0.1, 1.0, 10.0]}, cv=2, refit=False,\n"
            "    backend='tpu', config=cfg).fit(X, y)\n"
            "rep = locks.get_recorder().report()\n"
            "assert not rep['inversions'], rep['inversions']\n"
            "print('EDGES', rep['n_edges'])\n")
        env = dict(__import__("os").environ,
                   SST_LOCKCHECK="1", JAX_PLATFORMS="cpu")
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, env=env,
                             cwd=str(REPO), timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "EDGES" in out.stdout


# ---------------------------------------------------------------------------
# key-flow analysis (keyflow rules)
# ---------------------------------------------------------------------------


KEYCHECK_FIXTURE = (
    "KEY_SURFACES = {\n"
    "    'cache': {\n"
    "        'relpath': 'a.py',\n"
    "        'anchor': '_cached_program',\n"
    "        'config_fields': ('alpha',),\n"
    "        'key_tokens': {},\n"
    "        'aliases': {'mesh_desc': 'mesh'},\n"
    "        'dataflow': True,\n"
    "    },\n"
    "}\n")


def keyflow_project(root, **kw):
    kc = write(root, "pkg/utils/keycheck.py", KEYCHECK_FIXTURE)
    return make_project(root, keycheck_path=kc, **kw)


class TestKeyflowRules:
    def test_declared_field_missing_and_fixed(self, tmp_path):
        write(tmp_path, "pkg/a.py", (
            "def _cached_program(key, build, store_parts=None):\n"
            "    return build()\n"
            "def use(config, mesh):\n"
            "    return _cached_program(('fit', mesh),\n"
            "                           lambda: jit(fn))\n"))
        proj = keyflow_project(tmp_path)
        r = lint(proj, ["key-part-missing"])
        hits = rule_hits(r, "key-part-missing")
        assert any(f["key"].endswith("cache:alpha") for f in hits), hits
        write(tmp_path, "pkg/a.py", (
            "def _cached_program(key, build, store_parts=None):\n"
            "    return build()\n"
            "def use(config, mesh):\n"
            "    return _cached_program(('fit', config.alpha, mesh),\n"
            "                           lambda: jit(fn))\n"))
        r2 = lint(proj, ["key-part-missing"])
        assert not rule_hits(r2, "key-part-missing")

    def test_closure_read_must_flow_into_key(self, tmp_path):
        write(tmp_path, "pkg/a.py", (
            "def _cached_program(key, build, store_parts=None):\n"
            "    return build()\n"
            "def use(config):\n"
            "    def fn(x, beta=config.beta):\n"
            "        return x * beta\n"
            "    return _cached_program(('fit', config.alpha),\n"
            "                           lambda: jit(fn))\n"))
        proj = keyflow_project(tmp_path)
        r = lint(proj, ["key-part-missing"])
        hits = rule_hits(r, "key-part-missing")
        assert any("config.beta" in f["message"] for f in hits), hits
        # keyed -> clean
        write(tmp_path, "pkg/a.py", (
            "def _cached_program(key, build, store_parts=None):\n"
            "    return build()\n"
            "def use(config):\n"
            "    def fn(x, beta=config.beta):\n"
            "        return x * beta\n"
            "    return _cached_program(\n"
            "        ('fit', config.alpha, config.beta),\n"
            "        lambda: jit(fn))\n"))
        r2 = lint(proj, ["key-part-missing"])
        assert not rule_hits(r2, "key-part-missing")

    def test_closure_resolution_is_scope_aware(self, tmp_path):
        # two builders reuse the helper name `step`; only builder b's
        # own `step` reads config.beta — builder a must stay clean
        write(tmp_path, "pkg/a.py", (
            "def _cached_program(key, build, store_parts=None):\n"
            "    return build()\n"
            "def builder_a(config):\n"
            "    def step(x):\n"
            "        return x\n"
            "    def fn(x):\n"
            "        return step(x)\n"
            "    return _cached_program(('a', config.alpha),\n"
            "                           lambda: jit(fn))\n"
            "def builder_b(config):\n"
            "    def step(x, beta=config.beta):\n"
            "        return x * beta\n"
            "    def fn(x):\n"
            "        return step(x)\n"
            "    return _cached_program(('b', config.alpha),\n"
            "                           lambda: jit(fn))\n"))
        proj = keyflow_project(tmp_path)
        r = lint(proj, ["key-part-missing"])
        hits = rule_hits(r, "key-part-missing")
        assert len(hits) == 1, hits
        assert "builder_b" in hits[0]["key"]

    def test_store_parts_drift_detected_via_alias(self, tmp_path):
        # the exact shape of the mesh drift the real tree carried: the
        # store key names mesh_desc, the in-memory key has no mesh
        write(tmp_path, "pkg/a.py", (
            "def _cached_program(key, build, store_parts=None):\n"
            "    return build()\n"
            "def use(config, mesh, mesh_desc):\n"
            "    return _cached_program(\n"
            "        ('fit', config.alpha),\n"
            "        lambda: jit(fn),\n"
            "        store_parts=('fit', mesh_desc))\n"))
        proj = keyflow_project(tmp_path)
        r = lint(proj, ["key-part-missing"])
        hits = rule_hits(r, "key-part-missing")
        assert any("mesh_desc" in f["message"] for f in hits), hits
        # the alias map accepts the in-memory twin name
        write(tmp_path, "pkg/a.py", (
            "def _cached_program(key, build, store_parts=None):\n"
            "    return build()\n"
            "def use(config, mesh, mesh_desc):\n"
            "    return _cached_program(\n"
            "        ('fit', config.alpha, mesh),\n"
            "        lambda: jit(fn),\n"
            "        store_parts=('fit', mesh_desc))\n"))
        r2 = lint(proj, ["key-part-missing"])
        assert not rule_hits(r2, "key-part-missing")

    def test_key_part_dead(self, tmp_path):
        write(tmp_path, "pkg/a.py", (
            "def _cached_program(key, build, store_parts=None):\n"
            "    return build()\n"
            "def use(config):\n"
            "    return _cached_program(\n"
            "        ('fit', config.alpha, config.gamma),\n"
            "        lambda: jit(fn))\n"))
        proj = keyflow_project(tmp_path)
        r = lint(proj, ["key-part-dead"])
        hits = rule_hits(r, "key-part-dead")
        assert any(f["key"].endswith("cache:gamma") for f in hits)
        assert not any(f["key"].endswith("cache:alpha") for f in hits)

    def test_registry_hygiene(self, tmp_path):
        write(tmp_path, "pkg/utils/keycheck.py", (
            "KEY_SURFACES = {\n"
            "    'ghost': {'relpath': 'gone.py', 'anchor': 'nope',\n"
            "              'config_fields': ('bogus',)},\n"
            "}\n"))
        write(tmp_path, "pkg/a.py", (
            "class TpuConfig:\n"
            "    alpha: int = 0\n"
            "def _cached_program(key, build):\n"
            "    return build()\n"
            "def use(config):\n"
            "    return _cached_program(('k', config.alpha),\n"
            "                           lambda: jit(fn))\n"))
        proj = make_project(
            tmp_path,
            keycheck_path=tmp_path / "pkg/utils/keycheck.py")
        r = lint(proj, ["key-surface-unregistered"])
        hits = rule_hits(r, "key-surface-unregistered")
        # stale relpath + uncovered _cached_program call site
        assert any(f["key"].endswith("ghost:relpath") for f in hits)
        assert any("callsite:" in f["key"] for f in hits)

    def test_unknown_config_field_flagged(self, tmp_path):
        write(tmp_path, "pkg/utils/keycheck.py", (
            "KEY_SURFACES = {\n"
            "    'cache': {'relpath': 'a.py',\n"
            "              'anchor': '_cached_program',\n"
            "              'config_fields': ('bogus',),\n"
            "              'dataflow': True},\n"
            "}\n"))
        write(tmp_path, "pkg/a.py", (
            "class TpuConfig:\n"
            "    alpha: int = 0\n"
            "def _cached_program(key, build):\n"
            "    return build()\n"
            "def use(config):\n"
            "    return _cached_program(('k', config.bogus),\n"
            "                           lambda: jit(fn))\n"))
        proj = make_project(
            tmp_path,
            keycheck_path=tmp_path / "pkg/utils/keycheck.py")
        r = lint(proj, ["key-surface-unregistered"])
        assert any(f["key"].endswith("cache:field:bogus")
                   for f in rule_hits(r, "key-surface-unregistered"))

    def test_note_missing_and_present(self, tmp_path):
        write(tmp_path, "pkg/a.py", (
            "def _cached_program(key, build, store_parts=None):\n"
            "    return build()\n"
            "def use(config):\n"
            "    return _cached_program(('fit', config.alpha),\n"
            "                           lambda: jit(fn))\n"))
        proj = keyflow_project(tmp_path)
        r = lint(proj, ["keycheck-note-missing"])
        assert rule_hits(r, "keycheck-note-missing")
        write(tmp_path, "pkg/a.py", (
            "from pkg.utils import keycheck\n"
            "def _cached_program(key, build, store_parts=None):\n"
            "    keycheck.note('cache', key)\n"
            "    return build()\n"
            "def use(config):\n"
            "    return _cached_program(('fit', config.alpha),\n"
            "                           lambda: jit(fn))\n"))
        r2 = lint(proj, ["keycheck-note-missing"])
        assert not rule_hits(r2, "keycheck-note-missing")

    def test_suppression_honored(self, tmp_path):
        write(tmp_path, "pkg/a.py", (
            "def _cached_program(key, build, store_parts=None):\n"
            "    return build()\n"
            "def use(config):\n"
            "    # the key is completed downstream (see helper)\n"
            "    # sstlint: disable=key-part-missing\n"
            "    return _cached_program(('fit',),\n"
            "                           lambda: jit(fn))\n"))
        proj = keyflow_project(tmp_path)
        r = lint(proj, ["key-part-missing"])
        assert not rule_hits(r, "key-part-missing")

    def test_rules_skip_without_registry(self, tmp_path):
        write(tmp_path, "pkg/a.py", (
            "def _cached_program(key, build):\n"
            "    return build()\n"
            "def use(config):\n"
            "    return _cached_program(('k', config.alpha),\n"
            "                           lambda: jit(fn))\n"))
        proj = make_project(tmp_path)      # no keycheck_path
        r = lint(proj, ["key-part-missing", "key-part-dead",
                        "key-surface-unregistered",
                        "keycheck-note-missing"])
        assert r["n_findings"] == 0

    def test_cli_seeded_key_part_missing_fails(self, tmp_path):
        """The acceptance fixture: a spark_sklearn_tpu/-shaped tree
        with a declared key-feeding field that never reaches its key
        must fail the CLI (exit 1) with a key-part-missing finding."""
        write(tmp_path, "spark_sklearn_tpu/utils/keycheck.py", (
            "KEY_SURFACES = {\n"
            "    'cache': {'relpath': 'a.py',\n"
            "              'anchor': '_cached_program',\n"
            "              'config_fields': ('alpha',),\n"
            "              'dataflow': True},\n"
            "}\n"))
        write(tmp_path, "spark_sklearn_tpu/a.py", (
            "def _cached_program(key, build):\n"
            "    return build()\n"
            "def use(config):\n"
            "    return _cached_program(('fit',), lambda: jit(fn))\n"))
        write(tmp_path, ".gitignore", "__pycache__/\n*.pyc\n")
        out = subprocess.run(
            [sys.executable, "-m", "tools.sstlint", "--format", "json",
             str(tmp_path / "spark_sklearn_tpu")],
            capture_output=True, text=True, cwd=str(REPO), timeout=180)
        assert out.returncode == 1, out.stdout + out.stderr
        payload = json.loads(out.stdout)
        assert any(f["rule"] == "key-part-missing"
                   for f in payload["findings"]), payload["findings"]


# ---------------------------------------------------------------------------
# journal-format registry rules
# ---------------------------------------------------------------------------


JOURNALSPEC_FIXTURE = (
    "def _d(v):\n"
    "    return v\n"
    "CHECKPOINT_RECORD_KINDS = {\n"
    "    'fault': {'version': 1, 'discriminator': 'fault_chunk_id',\n"
    "              'decode': _d},\n"
    "}\n"
    "CHECKPOINT_META_KINDS = {\n"
    "    'plan': {'version': 1, 'prefix_match': False, 'decode': _d},\n"
    "    'px:': {'version': 1, 'prefix_match': True, 'decode': _d},\n"
    "}\n"
    "SERVICE_RECORD_KINDS = {\n"
    "    'submitted': {'version': 1, 'decode': _d},\n"
    "}\n")


def journal_project(root, **kw):
    js = write(root, "pkg/utils/journalspec.py", JOURNALSPEC_FIXTURE)
    return make_project(root, journalspec_path=js, **kw)


class TestJournalRules:
    def test_undeclared_kinds_flagged(self, tmp_path):
        write(tmp_path, "pkg/a.py", (
            "def save(ckpt, j, fp):\n"
            "    ckpt.put_meta('plan', 1)\n"
            "    ckpt.put_meta(f'px:{fp}', 2)\n"
            "    ckpt.put_meta('rogue', 3)\n"
            "    j.append('submitted', {})\n"
            "    j.append('rogue_kind', {})\n"
            "    xs = []\n"
            "    xs.append('plain_list_item')\n"))
        proj = journal_project(tmp_path)
        r = lint(proj, ["journal-format"])
        hits = rule_hits(r, "journal-format")
        keys = {f["key"] for f in hits}
        assert any(k.endswith("meta:rogue") for k in keys), keys
        assert any(k.endswith("service:rogue_kind") for k in keys)
        # declared kinds + 1-arg list.append stay clean
        assert len(hits) == 2, hits

    def test_fstring_prefix_requires_prefix_entry(self, tmp_path):
        write(tmp_path, "pkg/a.py", (
            "def save(ckpt, fp):\n"
            "    ckpt.put_meta(f'plan{fp}', 1)\n"))
        proj = journal_project(tmp_path)
        r = lint(proj, ["journal-format"])
        # 'plan' is declared exact-only: its f-string variants are
        # undeclared dynamic kinds
        assert rule_hits(r, "journal-format")

    def test_suppression_honored(self, tmp_path):
        write(tmp_path, "pkg/a.py", (
            "def save(ckpt):\n"
            "    # migration shim writes the legacy kind on purpose\n"
            "    # sstlint: disable=journal-format\n"
            "    ckpt.put_meta('legacy', 1)\n"))
        proj = journal_project(tmp_path)
        r = lint(proj, ["journal-format"])
        assert not rule_hits(r, "journal-format")

    def test_decoder_and_dead_entry_checks(self, tmp_path):
        write(tmp_path, "pkg/utils/journalspec.py", (
            "def _d(v):\n"
            "    return v\n"
            "CHECKPOINT_RECORD_KINDS = {\n"
            "    'fault': {'version': 1, 'decode': _d},\n"
            "    'broken': {'version': 'one'},\n"
            "}\n"
            "CHECKPOINT_META_KINDS = {\n"
            "    'plan': {'version': 1, 'prefix_match': False,\n"
            "             'decode': _d},\n"
            "    'never_written': {'version': 1,\n"
            "                      'prefix_match': False,\n"
            "                      'decode': _d},\n"
            "}\n"
            "SERVICE_RECORD_KINDS = {\n"
            "    'submitted': {'version': 1, 'decode': _d},\n"
            "    'ghost': {'version': 1, 'decode': _d},\n"
            "}\n"))
        write(tmp_path, "pkg/a.py", (
            "def save(ckpt, j):\n"
            "    ckpt.put_meta('plan', 1)\n"
            "    j.append('submitted', {})\n"))
        proj = make_project(
            tmp_path,
            journalspec_path=tmp_path / "pkg/utils/journalspec.py")
        r = lint(proj, ["journal-decoder-missing"])
        keys = {f["key"] for f in rule_hits(r, "journal-decoder-missing")}
        assert any("broken:version" in k for k in keys), keys
        assert any("broken:decode" in k for k in keys)
        assert any("meta-dead:never_written" in k for k in keys)
        assert any("service-dead:ghost" in k for k in keys)
        assert not any(":plan:" in k or "meta-dead:plan" in k
                       for k in keys)

    def test_rules_skip_without_registry(self, tmp_path):
        write(tmp_path, "pkg/a.py", (
            "def save(ckpt):\n"
            "    ckpt.put_meta('anything_goes', 1)\n"))
        proj = make_project(tmp_path)
        r = lint(proj, ["journal-format", "journal-decoder-missing"])
        assert r["n_findings"] == 0

    def test_real_registry_declares_every_write_site(self):
        """Every put_meta/append kind the real tree writes is declared
        (the rule found two undeclared service kinds — lease and
        shutdown — when it first ran; they are registered now)."""
        from spark_sklearn_tpu.utils import journalspec
        assert "lease" in journalspec.SERVICE_RECORD_KINDS
        assert "shutdown" in journalspec.SERVICE_RECORD_KINDS
        r = run_lint(root=REPO, rules=["journal-format",
                                       "journal-decoder-missing"])
        assert r["n_findings"] == 0, r["findings"]


# ---------------------------------------------------------------------------
# escape-hatch audit rules
# ---------------------------------------------------------------------------


CONFIG_FIXTURE = (
    "class TpuConfig:\n"
    "    alpha: int = 0\n"
    "    fusion: bool = True\n")


class TestHatchRules:
    def test_unregistered_claim_flagged(self, tmp_path):
        from tools.sstlint.project import EscapeHatch
        write(tmp_path, "pkg/config.py", CONFIG_FIXTURE)
        readme = write(tmp_path, "README.md", (
            "# pkg\n"
            "`fusion` off is a byte-identical escape hatch.\n"))
        proj = make_project(tmp_path, readme=readme)
        r = lint(proj, ["escape-hatch-unregistered"])
        hits = rule_hits(r, "escape-hatch-unregistered")
        assert any("fusion" in f["key"] for f in hits), hits
        # registering it (with a resolving test) clears the finding
        write(tmp_path, "tests/test_f.py",
              "def test_parity():\n    pass\n")
        proj2 = make_project(
            tmp_path, readme=readme,
            escape_hatches=(EscapeHatch(
                "fusion", "fusion", "tests/test_f.py::test_parity"),))
        r2 = lint(proj2, ["escape-hatch-unregistered",
                          "escape-hatch-untested"])
        assert r2["n_findings"] == 0, r2["findings"]

    def test_docstring_claims_audited(self, tmp_path):
        write(tmp_path, "pkg/config.py", CONFIG_FIXTURE)
        write(tmp_path, "pkg/a.py", (
            '"""Module.\n'
            "\n"
            "``fusion`` off is an exact no-op.\n"
            '"""\n'))
        proj = make_project(tmp_path)
        r = lint(proj, ["escape-hatch-unregistered"])
        assert rule_hits(r, "escape-hatch-unregistered")

    def test_unanchored_prose_skipped(self, tmp_path):
        write(tmp_path, "pkg/config.py", CONFIG_FIXTURE)
        readme = write(tmp_path, "README.md", (
            "Results are byte-identical across restarts by design.\n"))
        proj = make_project(tmp_path, readme=readme)
        r = lint(proj, ["escape-hatch-unregistered"])
        assert not rule_hits(r, "escape-hatch-unregistered")

    def test_dangling_pointer_and_bad_knob(self, tmp_path):
        from tools.sstlint.project import EscapeHatch
        write(tmp_path, "pkg/config.py", CONFIG_FIXTURE)
        write(tmp_path, "tests/test_f.py",
              "def test_other():\n    pass\n")
        proj = make_project(tmp_path, escape_hatches=(
            EscapeHatch("a", "fusion", "tests/test_f.py::test_gone"),
            EscapeHatch("b", "fusion", "tests/test_missing.py::test_x"),
            EscapeHatch("c", "not_a_knob", "tests/test_f.py::test_other"),
        ))
        r = lint(proj, ["escape-hatch-untested"])
        keys = {f["key"] for f in rule_hits(r, "escape-hatch-untested")}
        assert any("a:test" in k for k in keys), keys
        assert any("b:file" in k for k in keys)
        assert any("c:knob" in k for k in keys)

    def test_real_tree_hatches_resolve(self):
        """Every registered hatch in the real project map points at a
        parity test that exists (including the two the audit itself
        surfaced: geometry_fixed and runlog_dir)."""
        proj = Project.default(REPO)
        names = {h.name for h in proj.escape_hatches}
        assert {"fusion", "prefix_reuse", "chunk_loop",
                "geometry_fixed", "runlog_dir"} <= names
        r = run_lint(root=REPO, rules=["escape-hatch-untested",
                                       "escape-hatch-unregistered"])
        assert r["n_findings"] == 0, r["findings"]


# ---------------------------------------------------------------------------
# runtime key-flow recorder (SST_KEYCHECK)
# ---------------------------------------------------------------------------


class TestKeycheckRuntime:
    def _recorder(self):
        from spark_sklearn_tpu.utils.keycheck import KeyFlowRecorder
        return KeyFlowRecorder()

    def test_collision_detected_once_per_signature(self):
        rec = self._recorder()
        rec.note("s", ("a",), fields={"x": 1}, detail="first")
        rec.note("s", ("a",), fields={"x": 2}, detail="second")
        rec.note("s", ("a",), fields={"x": 2}, detail="repeat")
        rep = rec.report()
        assert len(rep["collisions"]) == 1, rep["collisions"]
        col = rep["collisions"][0]
        assert col["fields_a"] == {"x": 1}
        assert col["fields_b"] == {"x": 2}

    def test_same_fields_never_collide(self):
        rec = self._recorder()
        for _ in range(5):
            rec.note("s", ("a",), fields={"x": 1})
        assert not rec.report()["collisions"]
        assert rec.report()["n_notes"] == 5
        assert rec.keys("s") and len(rec.keys("s")) == 1

    def test_fieldless_notes_record_without_collisions(self):
        rec = self._recorder()
        rec.note("s", ("a",))
        rec.note("s", ("a",))
        rec.note("s", ("b",))
        rep = rec.report()
        assert not rep["collisions"]
        assert len(rec.keys("s")) == 2

    def test_distinct_keys_no_collision_and_reset(self):
        rec = self._recorder()
        rec.note("s", ("a",), fields={"x": 1})
        rec.note("s", ("b",), fields={"x": 2})
        assert not rec.report()["collisions"]
        rec.reset()
        rep = rec.report()
        assert rep["n_notes"] == 0 and rep["n_keys"] == 0

    def test_note_is_env_gated(self, monkeypatch):
        from spark_sklearn_tpu.utils import keycheck
        rec = keycheck.get_recorder()
        rec.reset()
        monkeypatch.delenv("SST_KEYCHECK", raising=False)
        keycheck.note("s", ("off",), fields={"x": 1})
        assert rec.report()["n_notes"] == 0
        monkeypatch.setenv("SST_KEYCHECK", "1")
        keycheck.note("s", ("on",), fields={"x": 1})
        assert rec.report()["n_notes"] == 1
        rec.reset()

    def test_seeded_collision_fails_pytest_session(self, tmp_path):
        """The conftest hook: a green test that recorded a key
        collision under SST_KEYCHECK=1 must flip the session red."""
        import uuid
        seed = REPO / "tests" / \
            f"test_keycheck_seed_{uuid.uuid4().hex[:8]}.py"
        seed.write_text(
            "from spark_sklearn_tpu.utils import keycheck\n"
            "def test_seeded_collision():\n"
            "    keycheck.note('program_cache', ('k',),\n"
            "                  fields={'bf16': False})\n"
            "    keycheck.note('program_cache', ('k',),\n"
            "                  fields={'bf16': True})\n")
        env = dict(__import__("os").environ, SST_KEYCHECK="1",
                   JAX_PLATFORMS="cpu")
        try:
            out = subprocess.run(
                [sys.executable, "-m", "pytest", str(seed), "-q",
                 "-p", "no:cacheprovider"],
                capture_output=True, text=True, env=env,
                cwd=str(REPO), timeout=300)
        finally:
            seed.unlink()
        assert out.returncode == 1, out.stdout[-2000:] + out.stderr[-500:]
        assert "COLLISION" in out.stdout, out.stdout[-2000:]
        assert "1 passed" in out.stdout, out.stdout[-2000:]

    def test_engine_keys_clean_and_knob_toggles_key(self):
        """End-to-end: two real compiled searches under SST_KEYCHECK=1
        — zero collisions, every expected surface reports, and
        toggling a declared key-feeding knob (bf16_matmul) changes the
        recorded program-cache AND checkpoint key sets."""
        code = (
            "import os\n"
            "import numpy as np\n"
            "import jax\n"
            "jax.config.update('jax_platforms', 'cpu')\n"
            "from sklearn.linear_model import LogisticRegression\n"
            "import spark_sklearn_tpu as sst\n"
            "from spark_sklearn_tpu.utils import keycheck\n"
            "X = np.random.RandomState(0).randn(64, 4)"
            ".astype(np.float32)\n"
            "y = (X[:, 0] > 0).astype(np.int64)\n"
            "rec = keycheck.get_recorder()\n"
            "keysets = {}\n"
            "for bf16 in (False, True):\n"
            "    rec.reset()\n"
            "    cfg = sst.TpuConfig(bf16_matmul=bf16,\n"
            "        checkpoint_dir=f'/tmp/kc_ckpt_{os.getpid()}_"
            "{int(bf16)}')\n"
            "    sst.GridSearchCV(LogisticRegression(max_iter=5),\n"
            "        {'C': [0.1, 1.0]}, cv=2, refit=False,\n"
            "        backend='tpu', config=cfg).fit(X, y)\n"
            "    rep = rec.report()\n"
            "    assert not rep['collisions'], rep['collisions']\n"
            "    assert rep['n_notes'] > 0\n"
            "    keysets[bf16] = {\n"
            "        s: rec.keys(s) for s in ('program_cache',\n"
            "                                 'checkpoint',\n"
            "                                 'plan_key')}\n"
            "for s in ('program_cache', 'checkpoint', 'plan_key'):\n"
            "    assert keysets[False][s], s + ' recorded no keys'\n"
            "for s in ('program_cache', 'checkpoint'):\n"
            "    assert keysets[False][s] != keysets[True][s], (\n"
            "        s + ' key set identical across bf16 toggle')\n"
            "print('SURFACES',\n"
            "      sorted(k for k, v in keysets[False].items() if v))\n")
        env = dict(__import__("os").environ,
                   SST_KEYCHECK="1", JAX_PLATFORMS="cpu")
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, env=env,
                             cwd=str(REPO), timeout=540)
        assert out.returncode == 0, out.stderr[-3000:]
        assert "SURFACES" in out.stdout
