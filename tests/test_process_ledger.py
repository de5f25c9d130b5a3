"""The process ledger (ISSUE 37): what a process pays once, recorded
always (``obs/process.py``), handed out as ``search_report["process"]``.

  - one set of jax-monitoring listeners for the program; a persistent
    cache load counted ONCE (jax records the retrieval inside the
    back-end compile's duration), nested traces counted once;
  - ``compile.wait``: the seconds a dispatching thread stands for a
    build reach ``build_blocked_s`` with the tracer off, are a span with
    it on and ``sst.compile.wait`` under a live profiler;
  - a build on ``sst-compile`` is not ``blocking``, one on a thread
    inside ``fit`` is; a warm ``fit`` makes two ledger calls and adds
    no record;
  - the block validates against ``PROCESS_BLOCK_SCHEMA``; the records
    are bounded, the totals exact.

XLA:CPU: every second here is a host second of this box, compared with
another second of the same process, never reported.
"""

import glob
import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import spark_sklearn_tpu as sst
from spark_sklearn_tpu.obs import process
from spark_sklearn_tpu.obs.metrics import (
    PROCESS_BLOCK_SCHEMA,
    SEARCH_REPORT_SCHEMA,
)
from spark_sklearn_tpu.obs.trace import get_tracer, search_tracing
from spark_sklearn_tpu.parallel import pipeline

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.dirname(os.path.abspath(sst.__file__))

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

RECORD_KEYS = {"name", "label", "thread", "search", "t0_s", "t1_s",
               "trace_s", "lower_s", "cache_load_s", "xla_s", "cache",
               "blocking"}


def _problem(n=150, d=7, k=3, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    y = rng.randint(0, k, size=n).astype(np.int64)
    X[np.arange(n), y] += 2.0
    return X, y


def _search():
    from sklearn.linear_model import LogisticRegression
    return sst.GridSearchCV(
        LogisticRegression(max_iter=5), {"C": [0.1, 1.0, 10.0]}, cv=3,
        refit=False, backend="tpu")


@pytest.fixture
def tracer_off():
    tracer = get_tracer()
    was = tracer.enabled
    tracer.disable()
    tracer.clear()
    yield tracer
    tracer.clear()
    if was:
        tracer.enable()
    else:
        tracer.disable()


@pytest.fixture
def fresh_ledger(monkeypatch):
    """A ledger of this test's own behind the process's listeners (the
    worker's process has built hundreds of programs by now, and a
    ledger keeps the 64 longest)."""
    ledger = process.ProcessLedger()
    monkeypatch.setattr(process, "_LEDGER", ledger)
    return ledger


@pytest.fixture
def slow_precompile(monkeypatch):
    """A build that takes 0.3 s on the compile thread and builds
    nothing."""
    def slow(jit_fn, *args):
        time.sleep(0.3)
        return "executable"
    monkeypatch.setattr(pipeline, "precompile", slow)


def _span(ledger, kind, seconds, name="f", inner=()):
    """Feed ``ledger`` one phase as jax would: the enter's scalar, the
    nested phases, the exit's time span."""
    ledger.on_scalar(kind, 0.0, fun_name=name)
    for args in inner:
        _span(ledger, *args)
    ledger.on_time_span(kind, 100.0, 100.0 + seconds, fun_name=name)


# ---------------------------------------------------------------------------
# a miss, then a hit: two processes on one cache directory
# ---------------------------------------------------------------------------

_CACHE_SCRIPT = textwrap.dedent("""
    import json, sys
    from jax._src import monitoring
    runner = {"trace_s": 0.0, "lower_s": 0.0, "backend_compile_s": 0.0,
              "cache_retrieval_s": 0.0}
    names = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "backend_compile_s",
        "/jax/compilation_cache/cache_retrieval_time_sec":
            "cache_retrieval_s"}
    def on_duration(event, duration, **kw):
        if event in names:
            runner[names[event]] += duration
    monitoring.register_event_duration_secs_listener(on_duration)

    import jax, jax.numpy as jnp
    import spark_sklearn_tpu as sst
    from spark_sklearn_tpu.parallel.pipeline import (
        enable_persistent_cache, persistent_cache_counts)
    enable_persistent_cache(sst.TpuConfig(persistent_cache_min_compile_s=0))

    def net(x, k):      # unrolled: executables of a size worth loading
        for i in range(k):
            x = jnp.tanh(x @ x.T) @ x + jnp.sin(x) * (i + 1.5)
        return x.sum()
    for k in (60, 90, 120):
        f = jax.jit(lambda x, k=k: net(x, k))
        f(jnp.ones((64, 64), jnp.float32)).block_until_ready()
    print(json.dumps({"process": sst.obs.process_report(),
                      "runner": runner,
                      "counts": persistent_cache_counts()}))
""")


@pytest.fixture(scope="module")
def miss_then_hit(tmp_path_factory):
    cache = str(tmp_path_factory.mktemp("jax-cache"))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": cache, "PYTHONPATH": ROOT}
    env.pop("XLA_FLAGS", None)
    out = []
    for _ in range(2):
        proc = subprocess.run([sys.executable, "-c", _CACHE_SCRIPT],
                              capture_output=True, text=True, env=env,
                              cwd=ROOT, timeout=600)
        assert proc.returncode == 0, proc.stderr[-3000:]
        out.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    return out


def test_miss_compiles_and_loads_nothing(miss_then_hit):
    miss = miss_then_hit[0]["process"]
    assert miss["n_cache_misses"] >= 3 and miss["n_cache_hits"] == 0
    assert miss["cache_load_s"] == 0.0
    assert miss["xla_s"] > 0.0
    assert miss_then_hit[0]["counts"]["misses"] >= 3


def test_hit_counts_each_load_once(miss_then_hit):
    hit, runner = miss_then_hit[1]["process"], miss_then_hit[1]["runner"]
    assert hit["n_cache_hits"] >= 3
    assert miss_then_hit[1]["counts"]["hits"] == hit["n_cache_hits"]
    # jax's own retrieval sum, once
    assert hit["cache_load_s"] == pytest.approx(
        runner["cache_retrieval_s"], rel=1e-6, abs=1e-6)
    # what is left of the back end on a hit is bookkeeping
    assert 0.0 <= hit["xla_s"] < 0.1 * hit["cache_load_s"]
    # the runner's sum holds every load twice (and every nested trace)
    ledger = (hit["trace_s"] + hit["lower_s"] + hit["xla_s"]
              + hit["cache_load_s"])
    doubled = sum(runner.values())
    assert ledger < doubled
    assert doubled - ledger >= 0.9 * hit["cache_load_s"]
    loaded = [b for b in hit["builds"] if b["cache"] == "hit"]
    assert len(loaded) >= 3
    assert all(b["xla_s"] >= 0.0 and b["cache_load_s"] > 0.0
               for b in loaded)


def test_import_parts_add_up(miss_then_hit):
    rep = miss_then_hit[0]["process"]
    assert rep["import_s"] > 0.0
    assert rep["import_own_s"] + sum(rep["import_by_root"].values()) \
        == pytest.approx(rep["import_s"], abs=1e-6)
    # the script imported jax (and numpy under it) before the package
    assert set(rep["import_by_root"]) == {
        "numpy", "jax", "jax.experimental.pallas", "scipy", "pandas",
        "sklearn"}
    assert rep["import_by_root"]["sklearn"] > 0.0
    # enable_persistent_cache was the first call, after the import
    assert rep["first_call_s"] >= rep["import_s"]
    # a build outside any fit blocks no search
    assert not any(b["blocking"] for b in rep["builds"])
    assert all(b["search"] is None for b in rep["builds"])


# ---------------------------------------------------------------------------
# the wait
# ---------------------------------------------------------------------------

def test_wait_reaches_the_ledger_with_the_tracer_off(
        tracer_off, slow_precompile):
    before = process.process_report()["build_blocked_s"]
    pipe = pipeline.ChunkPipeline(depth=2)
    fut = pipe.submit_precompile(object(), label="slow")
    assert process.join_build(fut, tracer_off) == "executable"
    pipe.close()
    assert len(tracer_off) == 0
    after = process.process_report()["build_blocked_s"]
    assert 0.2 < after - before < 5.0
    # a build that is done costs nothing and is no ledger call
    calls = process._LEDGER.calls
    assert process.join_build(fut, tracer_off) == "executable"
    assert process._LEDGER.calls == calls


@pytest.mark.parametrize("join", ["drain", "close"])
def test_wait_is_a_span_with_the_tracer_on(tracer_off, slow_precompile,
                                           join):
    tracer_off.enable()
    before = process.process_report()["build_blocked_s"]
    pipe = pipeline.ChunkPipeline(depth=2)
    pipe.submit_precompile(object(), label="slow")
    getattr(pipe, join)()
    pipe.close()
    waits = [e for e in tracer_off.events() if e[1] == "compile.wait"]
    assert len(waits) == 1
    assert waits[0][6]["where"] == join
    seconds = waits[0][3] - waits[0][2]
    assert 0.2 < seconds < 5.0
    assert process.process_report()["build_blocked_s"] - before \
        == pytest.approx(seconds, abs=0.05)
    # the build's own span says what it was
    compiles = [e for e in tracer_off.events() if e[1] == "compile"]
    assert compiles and set(compiles[0][6]) >= {
        "label", "cache", "cache_load_s", "trace_s", "lower_s"}


def test_wait_is_mirrored_into_a_live_profile(tracer_off, slow_precompile,
                                              tmp_path):
    from jax.profiler import ProfileData

    trace_dir = str(tmp_path)
    with jax.profiler.trace(trace_dir):
        pipe = pipeline.ChunkPipeline(depth=2)
        pipe.submit_precompile(object(), label="slow")
        pipe.drain()
        pipe.close()
    assert len(tracer_off) == 0
    path = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))[0]
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(ev.name for ev in line.events)
    assert "sst.compile.wait" in names
    assert "sst.compile" in names


# ---------------------------------------------------------------------------
# who builds: the compile thread, or a thread inside fit
# ---------------------------------------------------------------------------

def test_blocking_is_a_build_inside_fit_off_the_compile_thread(
        tracer_off, fresh_ledger):
    def ahead(x):
        return jnp.cos(x * 1.25).sum()

    def at_dispatch(x):
        return jnp.sin(x * 2.5).sum()

    x = jnp.ones((17,), jnp.float32)
    with search_tracing(None):
        pipe = pipeline.ChunkPipeline(depth=2)
        fut = pipe.submit_precompile(jax.jit(ahead), x, label="ahead")
        fut.result()
        pipe.close()
        jax.jit(at_dispatch)(x).block_until_ready()
    jax.jit(lambda x: jnp.tan(x * 0.75).sum())(x).block_until_ready()
    builds = process.process_report()["builds"]
    by_name = {b["name"]: b for b in builds}
    a, d = by_name["jit(ahead)"], by_name["jit(at_dispatch)"]
    assert a["thread"].startswith("sst-compile") and not a["blocking"]
    assert a["label"] == "ahead" and a["search"] is not None
    assert d["blocking"] and d["search"] == a["search"]
    assert d["label"] is None
    outside = by_name["jit(<lambda>)"]
    assert outside["search"] is None and not outside["blocking"]
    for b in (a, d):
        assert set(b) == RECORD_KEYS
        assert b["trace_s"] > 0.0 and b["lower_s"] > 0.0
        assert b["t1_s"] - b["t0_s"] >= b["trace_s"] + b["lower_s"]


def test_second_fit_adds_no_record_and_makes_two_calls(tracer_off):
    X, y = _problem()
    first = _search().fit(X, y).search_report["process"]
    calls = process._LEDGER.calls
    second = _search().fit(X, y).search_report["process"]
    # no build in flight: the fit's two stamps and nothing else
    assert process._LEDGER.calls - calls == 2
    for key in ("n_programs", "n_cache_hits", "n_cache_misses", "trace_s",
                "lower_s", "xla_s", "cache_load_s", "build_union_s",
                "build_blocked_s", "import_s", "first_call_s"):
        assert second[key] == first[key], key
    assert second["builds"] == first["builds"]
    assert len(second["fits"]) == min(8, len(first["fits"]) + 1) or \
        len(first["fits"]) == 8
    done = [f for f in second["fits"] if f["t1_s"] is not None]
    assert all(f["t1_s"] >= f["t0_s"] for f in done)


def test_block_validates_against_the_schema(tracer_off):
    X, y = _problem()
    rep = _search().fit(X, y).search_report
    assert "process" in {d.name for d in SEARCH_REPORT_SCHEMA}
    block = rep["process"]
    assert list(block) == [d.name for d in PROCESS_BLOCK_SCHEMA]
    assert block == {**process.process_report(), "fits": block["fits"]}
    for b in block["builds"]:
        assert set(b) == RECORD_KEYS
        assert b["cache"] in ("hit", "miss", "off")
        assert min(b["trace_s"], b["lower_s"], b["xla_s"],
                   b["cache_load_s"]) >= 0.0
    assert block["build_blocked_s"] >= 0.0
    assert block["build_union_s"] >= 0.0
    json.dumps(block)
    # the host tier carries it too
    from sklearn.tree import DecisionTreeClassifier
    host = sst.GridSearchCV(DecisionTreeClassifier(), {"max_depth": [2, 3]},
                            cv=3, refit=False).fit(X, y)
    assert host.search_report["backend"] == "host"
    assert list(host.search_report["process"]) == list(block)


# ---------------------------------------------------------------------------
# the ledger alone, fed as jax feeds it
# ---------------------------------------------------------------------------

def test_records_are_bounded_and_totals_exact():
    ledger = process.ProcessLedger()
    n = 3 * process.MAX_BUILDS
    for i in range(n):
        _span(ledger, _TRACE, 0.001 * (i + 1), f"f{i}")
        _span(ledger, _LOWER, 0.002, f"f{i}")
        _span(ledger, _BACKEND, 0.004, f"f{i}")
    rep = ledger.report()
    assert len(rep["builds"]) == process.MAX_BUILDS
    assert rep["n_programs"] == n
    assert rep["trace_s"] == pytest.approx(0.001 * n * (n + 1) / 2)
    assert rep["lower_s"] == pytest.approx(0.002 * n)
    assert rep["xla_s"] == pytest.approx(0.004 * n)
    # the longest are the ones kept, in order of their start
    assert min(b["trace_s"] for b in rep["builds"]) \
        == pytest.approx(0.001 * (n - process.MAX_BUILDS + 1))
    starts = [b["t0_s"] for b in rep["builds"]]
    assert starts == sorted(starts)
    assert 0.0 < rep["build_union_s"]


def test_nested_phases_and_the_load_are_counted_once():
    ledger = process.ProcessLedger()
    # an outer trace of 1.0 s holds an inner jit's trace (0.2 s) and a
    # small eager program built on the way (0.05 + 0.05 + 0.1 s)
    _span(ledger, _TRACE, 1.0, "outer", inner=[
        (_TRACE, 0.2, "inner"),
        (_TRACE, 0.05, "eager"), (_LOWER, 0.05, "eager"),
        (_BACKEND, 0.1, "eager")])
    _span(ledger, _LOWER, 0.5, "outer")
    # the outer program is a cache hit: 0.3 s of retrieval inside a
    # back end of 0.31 s
    ledger.on_scalar(_BACKEND, 0.0, fun_name="outer")
    ledger.on_event("/jax/compilation_cache/compile_requests_use_cache")
    ledger.on_event("/jax/compilation_cache/cache_hits")
    ledger.on_duration(_RETRIEVAL, 0.3)
    ledger.on_time_span(_BACKEND, 100.0, 100.31, fun_name="outer")
    rep = ledger.report()
    assert rep["n_programs"] == 2
    assert rep["n_cache_hits"] == 1 and rep["n_cache_misses"] == 0
    # the outer trace less the eager program's lowering and back end
    assert rep["trace_s"] == pytest.approx(1.0 - 0.15)
    assert rep["lower_s"] == pytest.approx(0.55)
    assert rep["cache_load_s"] == pytest.approx(0.3)
    assert rep["xla_s"] == pytest.approx(0.1 + 0.01)
    # every second of the thread's wall once: 1.0 + 0.5 + 0.31
    assert sum(rep[k] for k in ("trace_s", "lower_s", "xla_s",
                                "cache_load_s")) == pytest.approx(1.81)
    eager, outer = sorted(rep["builds"], key=lambda b: b["name"])
    assert eager["cache"] == "off" and eager["trace_s"] \
        == pytest.approx(0.05)
    # the inner jit's trace is the outer program's
    assert outer["cache"] == "hit"
    assert outer["trace_s"] == pytest.approx(0.6 + 0.2)
    assert outer["lower_s"] == pytest.approx(0.5)
    assert outer["xla_s"] == pytest.approx(0.01)
    assert ledger.cache_events == {"hits": 1, "misses": 0}


def test_building_threads_lose_no_update():
    """More building threads than cores on one ledger, the interpreter
    switching every few bytecodes: every build counted, every second
    kept, each thread's phases paired with its own back end."""
    import threading

    ledger = process.ProcessLedger()
    n_threads, n_builds = 16, 200
    ready = threading.Barrier(n_threads)

    def worker(i):
        ready.wait(timeout=30)
        for j in range(n_builds):
            name = f"f{i}_{j}"
            _span(ledger, _TRACE, 0.25, name)
            _span(ledger, _LOWER, 0.125, name)
            ledger.on_scalar(_BACKEND, 0.0, fun_name=name)
            ledger.on_event(
                "/jax/compilation_cache/compile_requests_use_cache")
            if j % 2:
                ledger.on_event("/jax/compilation_cache/cache_hits")
                ledger.on_duration(_RETRIEVAL, 0.5)
            ledger.on_time_span(_BACKEND, 0.0, 0.5 + 0.0625,
                                fun_name=name)
            ledger.add_wait(0.03125)

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(was)
    n = n_threads * n_builds
    rep = ledger.report()
    assert rep["n_programs"] == n
    assert rep["n_cache_hits"] == n // 2 == ledger.cache_events["hits"]
    assert rep["n_cache_misses"] == n // 2
    assert rep["trace_s"] == 0.25 * n and rep["lower_s"] == 0.125 * n
    assert rep["cache_load_s"] == 0.5 * (n // 2)
    assert rep["xla_s"] == 0.0625 * (n // 2) + 0.5625 * (n // 2)
    assert rep["build_blocked_s"] == 0.03125 * n
    assert ledger.calls == 2 * n
    assert len(rep["builds"]) == process.MAX_BUILDS
    for b in rep["builds"]:        # its own thread's trace and lowering
        assert (b["trace_s"], b["lower_s"]) == (0.25, 0.125)
        assert b["cache_load_s"] in (0.0, 0.5)


def test_store_lookups_feed_the_same_totals():
    ledger = process.ProcessLedger()
    t = ledger.origin
    ledger.note_store("load", t + 1.0, t + 1.5, hit=True)
    ledger.note_store("load", t + 2.0, t + 2.1, hit=False)
    ledger.note_store("save", t + 3.0, t + 3.25)
    rep = ledger.report()
    assert rep["n_programs"] == 0
    assert rep["cache_load_s"] == pytest.approx(0.5)
    assert rep["xla_s"] == pytest.approx(0.35)
    assert rep["trace_s"] == rep["lower_s"] == 0.0
    assert [b["name"] for b in rep["builds"]] == [
        "programstore.load", "programstore.load", "programstore.save"]
    assert rep["build_union_s"] == pytest.approx(0.85)


# ---------------------------------------------------------------------------
# one place
# ---------------------------------------------------------------------------

def test_persistent_cache_counts_still_answers():
    counts = pipeline.persistent_cache_counts()
    assert set(counts) == {"hits", "misses"}
    assert counts == process.persistent_cache_counts()
    assert pipeline._CACHE_EVENTS is process._LEDGER.cache_events
    pipeline._install_cache_listener()        # the old name, harmless
    from spark_sklearn_tpu.parallel import persistent_cache_counts
    assert persistent_cache_counts() == counts


def test_listeners_are_registered_in_one_place():
    hits = []
    for path in glob.glob(os.path.join(PKG, "**", "*.py"), recursive=True):
        with open(path) as f:
            if "monitoring.register" in f.read():
                hits.append(os.path.relpath(path, PKG))
    assert hits == [os.path.join("obs", "process.py")]
    from jax._src import monitoring
    for listeners in (monitoring.get_event_listeners(),
                      monitoring.get_event_duration_listeners(),
                      monitoring.get_event_time_span_listeners(),
                      monitoring.get_scalar_listeners()):
        ours = [cb for cb in listeners
                if getattr(cb, "__module__", "") == process.__name__]
        assert len(ours) == 1
