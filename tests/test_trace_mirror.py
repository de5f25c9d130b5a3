"""The program's names in the profiler's trace (ISSUE 26).

  - host: while a ``jax.profiler`` trace is recorded, every span the
    vocabulary marks ``mirror`` is also a host event ``sst.<name>`` of
    that trace, stamped with the search's number; enclosing spans are
    not; with no profiler and the tracer off a site hands out the shared
    no-op span;
  - device: the launch program a search compiles carries every
    ``glm_lbfgs.*`` / ``sst.*`` named scope in its lowered text, and
    every scope in the vocabulary is one the package opens.

XLA:CPU has no device plane, so nothing here reads a device time.
"""

import ast
import glob
import os
import re

import jax
import numpy as np
import pytest

import spark_sklearn_tpu as sst
from spark_sklearn_tpu.obs import spans as span_vocab
from spark_sklearn_tpu.obs import trace as obs_trace
from spark_sklearn_tpu.obs.trace import Tracer, get_tracer

PKG = os.path.dirname(os.path.abspath(sst.__file__))
SCOPES = sorted(span_vocab.known_scope_names())


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


# ---------------------------------------------------------------------------
# host: spans mirrored into the profiler's trace
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def profiled_search(tmp_path_factory):
    """One tiny search under ``jax.profiler.trace`` with the in-memory
    tracer off: ``(host events [(name, stats)], events the tracer held,
    cv_results_)``."""
    from jax.profiler import ProfileData

    X, y = _problem()
    tracer = get_tracer()
    was = tracer.enabled
    tracer.disable()
    tracer.clear()
    trace_dir = str(tmp_path_factory.mktemp("profile"))
    try:
        _search().fit(X, y)                       # build the programs
        with jax.profiler.trace(trace_dir):
            search = _search().fit(X, y)
        recorded = len(tracer)
    finally:
        if was:
            tracer.enable()
    path = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))[0]
    events = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events.extend((ev.name, dict(ev.stats)) for ev in line.events
                          if ev.name.startswith("sst."))
    return events, recorded, search.cv_results_


@pytest.mark.parametrize("name", [
    "sst.stage", "sst.dispatch", "sst.gather", "sst.fit.prepare",
    "sst.fit.plan", "sst.fit.results", "sst.device_put.broadcast",
    "sst.dataplane.upload", "sst.dataplane.fingerprint"])
def test_profiler_trace_holds_mirrored_span(profiled_search, name):
    events, _, _ = profiled_search
    assert name in {n for n, _ in events}


@pytest.mark.parametrize("name", [
    "sst.search.fit",               # encloses every gap inside fit
    "sst.compute",                  # host-clock estimate, virtual track
    "sst.build_compile_groups",     # recorded after the fact
])
def test_profiler_trace_leaves_enclosing_span_out(profiled_search, name):
    events, _, _ = profiled_search
    assert not [n for n, _ in events if n.startswith(name + " ")
                or n == name]


def test_mirrored_spans_share_the_search_number(profiled_search):
    events, recorded, _ = profiled_search
    numbers = {stats.get("search") for _, stats in events}
    assert len(numbers) == 1 and None not in numbers
    # the worker threads' spans carry it too, with their own attributes
    stage = [stats for name, stats in events if name == "sst.stage"]
    assert stage and all("key" in s and "group" in s for s in stage)
    upload = [s for n, s in events if n == "sst.dataplane.upload"]
    assert upload and all(int(s["bytes"]) >= 0 for s in upload)
    # the profiler wrote them; the in-memory tracer stayed off and empty
    assert recorded == 0


def test_profiled_search_changes_no_score(profiled_search):
    _, _, profiled = profiled_search
    X, y = _problem()
    plain = _search().fit(X, y).cv_results_
    for key in plain:
        if "time" in key or key == "params":
            continue
        np.testing.assert_array_equal(
            np.asarray(plain[key]), np.asarray(profiled[key]), err_msg=key)


@pytest.mark.parametrize("name", ["stage", "fit.plan", "search.fit",
                                  "not.in.the.vocabulary"])
def test_site_is_the_shared_noop_without_profiler(name):
    """Tracer off, no profiler session: mirrored or not, a site returns
    the one shared no-op span and records nothing."""
    tr = Tracer()
    span = tr.span(name, key="k", group=0)
    assert span is obs_trace._NULL_SPAN
    with span as sp:
        assert sp.set(result=1) is sp
    assert len(tr) == 0


def test_enabled_tracer_records_and_mirrors(tmp_path):
    """Tracer on under a profiler session: the ring buffer holds the span
    with its attributes and no search number; ``set`` reaches both."""
    tr = Tracer()
    tr.enable()
    with jax.profiler.trace(str(tmp_path)):
        with tr.span("stage", key="k") as sp:
            assert sp._ann is not None
            sp.set(result=2)
        with tr.span("search.fit") as sp:
            assert sp._ann is None
    (_, name, _, _, _, _, attrs), (_, outer, *_rest) = tr.events()
    assert (name, outer) == ("stage", "search.fit")
    assert attrs == {"key": "k", "result": 2}


def test_mirrored_names_are_spans_opened_with_a_with():
    """A mirrored span is written by the profiler while it is open, so
    it cannot be one that a site records after the fact."""
    vocab = {d.name: d for d in span_vocab.SPAN_VOCABULARY}
    assert span_vocab.MIRRORED_SPANS
    for name in span_vocab.MIRRORED_SPANS:
        assert vocab[name].kind == "span", name
    retro = set()
    for path in glob.glob(os.path.join(PKG, "**", "*.py"), recursive=True):
        for node in ast.walk(ast.parse(open(path).read())):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in ("record_span", "record_async") \
                    and node.args \
                    and isinstance(node.args[0], ast.Constant):
                retro.add(node.args[0].value)
    assert not retro & span_vocab.MIRRORED_SPANS


def test_every_name_has_a_layer():
    for d in span_vocab.SPAN_VOCABULARY:
        assert d.layer, d.name
        assert d.kind in ("span", "instant", "async", "scope"), d.name


# ---------------------------------------------------------------------------
# device: named scopes in the launch program
# ---------------------------------------------------------------------------

def _lowered_launches(search):
    """Lowered text, debug info on, of the programs a tiny search hands
    to the compile thread (the fused fit + score launch among them)."""
    from spark_sklearn_tpu.parallel import pipeline

    texts = []
    original = pipeline.precompile

    def recording(jit_fn, *args):
        resolve = getattr(jit_fn, "resolve", None)
        fn = resolve(*args) if resolve is not None else jit_fn
        texts.append(fn.lower(*args).as_text(debug_info=True))
        return original(jit_fn, *args)

    pipeline.precompile = recording
    try:
        search()
    finally:
        pipeline.precompile = original
    assert texts, "the search precompiled no program"
    return "\n".join(texts)


#: the scopes of the kernel duals' launch; the others are the GLM launch's
DUAL_SCOPES = [s for s in SCOPES
               if s.startswith(("sst.svc.", "sst.box_fista."))]


@pytest.fixture(scope="module")
def lowered_launch():
    def search():
        # 40 candidates: convergence-sorted into several chunks, so the
        # group's fused program is compiled ahead on the compile thread
        from sklearn.linear_model import LogisticRegression
        X, y = _problem(n=131, d=5)
        sst.GridSearchCV(
            LogisticRegression(max_iter=5),
            {"C": np.logspace(-2, 1, 40).tolist()}, cv=3, refit=False,
            backend="tpu").fit(X, y)
    return _lowered_launches(search)


@pytest.fixture(scope="module")
def lowered_dual_launch():
    def search():
        # the narrowest chunks the mesh allows (a candidate a task shard)
        # and more candidates than one holds, so that a fused program is
        # compiled ahead
        from sklearn.svm import SVC
        X, y = _problem(n=90, d=5)
        # three classes of 30 rows: balanced, so the duals run in the
        # block-compact layout and `sst.svc.compact` is in the launch
        order = np.argsort(y, kind="stable")
        X, y = X[order], y[order]
        y[:] = np.arange(90) // 30
        sst.GridSearchCV(
            SVC(kernel="rbf", max_iter=5),
            {"C": np.logspace(-1, 2, 20).tolist()},
            cv=3, refit=False, backend="tpu",
            config=sst.TpuConfig(max_tasks_per_batch=3)).fit(X, y)
    return _lowered_launches(search)


#: the scopes of a compiled Pipeline(StandardScaler, MLPClassifier) search
MLP_SCOPES = [s for s in SCOPES
              if s.startswith(("sst.mlp.", "sst.prefix."))]


@pytest.fixture(scope="module")
def lowered_mlp_launch():
    import jax
    from sklearn.neural_network import MLPClassifier
    from sklearn.pipeline import Pipeline
    from sklearn.preprocessing import StandardScaler
    from spark_sklearn_tpu.models.base import resolve_family

    pipe = Pipeline([("scale", StandardScaler()),
                     ("mlp", MLPClassifier(hidden_layer_sizes=(4,),
                                           max_iter=2, random_state=0))])
    X, y = _problem(n=90, d=5)

    def search():
        # narrow chunks and more candidates than one holds, so that the
        # suffix family's fused program is compiled ahead
        sst.GridSearchCV(
            pipe, {"mlp__alpha": np.logspace(-4, -1, 20).tolist()},
            cv=3, refit=False, backend="tpu",
            config=sst.TpuConfig(max_tasks_per_batch=3)).fit(X, y)
    # the shared-prefix stage's program is launched where it is built,
    # not handed to the compile thread: lowered here
    family = resolve_family(pipe)
    stage = jax.jit(lambda data, w: family.prefix_transform({}, data, w))
    return _lowered_launches(search) + stage.lower(
        {"X": X}, np.ones((3, len(X)), np.float32)).as_text(debug_info=True)


#: the scopes of the binned tree grower
TREE_SCOPES = [s for s in SCOPES if s.startswith("sst.tree.")]


@pytest.fixture(scope="module")
def lowered_tree_launch():
    """A forest search with the grower on the kernels' form (interpreted:
    XLA:CPU runs the plain form, which sorts no rows and so opens no
    ``sst.tree.partition``)."""
    import functools
    from sklearn.ensemble import RandomForestClassifier
    from spark_sklearn_tpu.ops import tree_hist

    def search():
        X, y = _problem(n=90, d=5)
        sst.GridSearchCV(
            RandomForestClassifier(max_depth=2, random_state=0),
            {"n_estimators": list(range(1, 21))}, cv=3, refit=False,
            backend="tpu",
            config=sst.TpuConfig(max_tasks_per_batch=3)).fit(X, y)
    real = tree_hist.levels_of
    tree_hist.levels_of = functools.partial(
        tree_hist.GroupedLevels, tile=128, interpret=True)
    try:
        return _lowered_launches(search)
    finally:
        tree_hist.levels_of = real


#: the scopes of the boosting stage loop
BOOST_SCOPES = [s for s in SCOPES if s.startswith("sst.boost.")]


@pytest.fixture(scope="module")
def lowered_boost_launch():
    """A boosting search: a stage's gradients before its tree, the update
    of F after it."""
    from sklearn.ensemble import GradientBoostingClassifier

    def search():
        X, y = _problem(n=90, d=5)
        sst.GridSearchCV(
            GradientBoostingClassifier(max_depth=2, random_state=0),
            {"learning_rate": [0.1, 0.3], "n_estimators": [2, 3]}, cv=3,
            refit=False, backend="tpu",
            # a launch a run of one learning rate's two counts: two
            # chunks, so the second is compiled ahead
            config=sst.TpuConfig(devices=jax.devices()[:1],
                                 max_tasks_per_batch=6)
        ).fit(X, y > 0)
    return _lowered_launches(search)


#: the scopes of the neighbour search
KNN_SCOPES = [s for s in SCOPES if s.startswith("sst.knn.")]


@pytest.fixture(scope="module")
def lowered_knn_launch():
    """A KNeighborsClassifier search: a row block's distances, its
    selection and its votes, in narrow chunks so that a fused program is
    compiled ahead."""
    from sklearn.neighbors import KNeighborsClassifier

    def search():
        X, y = _problem(n=90, d=5)
        sst.GridSearchCV(
            KNeighborsClassifier(),
            {"n_neighbors": list(range(1, 11)),
             "weights": ["uniform", "distance"]}, cv=3, refit=False,
            backend="tpu",
            config=sst.TpuConfig(max_tasks_per_batch=3)).fit(X, y)
    return _lowered_launches(search)


def _launch_fixture(scope):
    return ("lowered_dual_launch" if scope in DUAL_SCOPES else
            "lowered_mlp_launch" if scope in MLP_SCOPES else
            "lowered_tree_launch" if scope in TREE_SCOPES else
            "lowered_boost_launch" if scope in BOOST_SCOPES else
            "lowered_knn_launch" if scope in KNN_SCOPES else
            "lowered_launch")


@pytest.mark.parametrize("scope", SCOPES)
def test_lowered_launch_holds_scope(request, scope):
    text = request.getfixturevalue(_launch_fixture(scope))
    # a scope opens a name stack inside a scanned body: no slash before it
    assert re.search(r'["/]' + re.escape(scope) + "/", text)


def test_scopes_in_the_package_are_the_vocabulary():
    """Every ``jax.named_scope`` literal the package opens is declared,
    and every declared scope is opened somewhere."""
    opened = set()
    for path in glob.glob(os.path.join(PKG, "**", "*.py"), recursive=True):
        for node in ast.walk(ast.parse(open(path).read())):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "named_scope":
                assert isinstance(node.args[0], ast.Constant), path
                opened.add(node.args[0].value)
    assert opened == set(SCOPES)
    assert all(s.startswith(("glm_lbfgs.", "sst.")) for s in SCOPES)
