"""``scopes.py`` and the four readers on it: the wire-format walk against a
message encoded by hand, the names on the trace PR 25 recorded
(``small_tpu_trace.xplane.pb``: no scope of the program in it), and every
scope and mirrored span on a small trace of a tiny search recorded on a TPU
v5e (``scoped_tpu_trace.xplane.pb``, by ``record_scoped_trace.py``).  No
test needs a chip; a trace with no device plane reads as ``None``."""

import os
import shutil

import pytest

import run
import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
SMALL = os.path.join(HERE, "small_tpu_trace.xplane.pb")
SCOPED = os.path.join(HERE, "scoped_tpu_trace.xplane.pb")
# the one instance the readers load through ``ctx["load_named"]``
scopes = run.load_file(os.path.join(BENCH, "scopes.py"))
NEW = ("solver.device_s", "solver.linesearch_share", "dataplane.upload_s",
       "search.named_idle_share")
SOLVER_SCOPES = (
    "glm_lbfgs.init", "glm_lbfgs.direction", "glm_lbfgs.forward",
    "glm_lbfgs.linesearch", "glm_lbfgs.step", "glm_lbfgs.gradient",
    "glm_lbfgs.backward", "glm_lbfgs.history")


# -- the wire format ----------------------------------------------------------

def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(num, value):
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    return varint(num << 3 | 2) + varint(len(value)) + value


def stat(meta_id, **kw):
    (kind, value), = kw.items()
    number = {"int": 4, "str": 5, "ref": 7}[kind]
    return field(1, meta_id) + field(
        number, value.encode() if kind == "str" else value)


def hand_made_space():
    """One device plane (a while with two body ops, one of them scoped)
    and one host plane (bench.search around an sst.stage)."""
    def entry(key, message):
        return field(1, key) + field(2, message)

    stats = {1: "tf_op", 2: "hlo_category", 3: "source", 4: "a name"}
    stat_meta = b"".join(
        field(5, entry(k, field(1, k) + field(2, v.encode())))
        for k, v in stats.items())
    ops = {
        1: ("%while.1 = (f32[4]) while(%t)",
            [stat(1, str="jit(f)/sst.fit/while")]),
        2: ("%fusion.7 = f32[4]{0} fusion(%p)",
            [stat(1, str="jit(f)/sst.fit/while/body/glm_lbfgs.forward/dot:"),
             stat(2, str="convolution fusion"), stat(3, str="solvers.py:9")]),
        3: ("%copy.2 = f32[4]{0} copy(%q)", [stat(2, ref=4)]),
    }
    op_meta = b"".join(
        field(4, entry(k, field(1, k) + field(2, name.encode())
                       + b"".join(field(5, s) for s in stats_)))
        for k, (name, stats_) in ops.items())

    def event(meta, offset_ps, duration_ps):
        return field(4, field(1, meta) + field(2, offset_ps)
                     + field(3, duration_ps))

    device_line = (field(2, b"XLA Ops") + field(3, 1_000)      # 1000 ns
                   + event(1, 0, 10_000_000)                   # 10 us
                   + event(2, 1_000_000, 4_000_000)
                   + event(3, 6_000_000, 3_000_000))
    other_line = field(2, b"XLA Modules") + field(3, 1_000) \
        + event(2, 0, 10_000_000)
    device = (field(2, b"/device:TPU:0") + field(3, device_line)
              + field(3, other_line) + stat_meta + op_meta)
    host_meta = b"".join(
        field(4, entry(k, field(1, k) + field(2, name)))
        for k, name in {1: b"bench.search", 2: b"sst.stage",
                        3: b"PjitFunction(f)"}.items())
    host_line = (field(2, b"python3") + field(3, 0)
                 + event(1, 500_000, 20_000_000)               # 0.5..20.5 us
                 + event(2, 0, 2_000_000)                      # clipped to 1.5
                 + event(3, 3_000_000, 1_000_000))
    host = field(2, b"/host:CPU") + field(3, host_line) + host_meta
    other = field(2, b"#Chip0 Misc") + field(3, device_line)
    return field(1, device) + field(1, host) + field(1, other)


def test_walk_of_a_hand_made_trace(tmp_path):
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(hand_made_space())
    planes = scopes.read_planes(str(path))
    assert [p["name"] for p in planes] == ["/device:TPU:0", "/host:CPU"]
    device, host = planes
    assert list(device["lines"]) == ["XLA Ops"]       # no other line read
    assert device["events"][2][1] == {
        "tf_op": "jit(f)/sst.fit/while/body/glm_lbfgs.forward/dot:",
        "hlo_category": "convolution fusion", "source": "solvers.py:9"}
    assert device["events"][3][1] == {"hlo_category": "a name"}   # a ref
    start = device["lines"]["XLA Ops"][1][1]
    assert start == pytest.approx(2e-6)               # 1000 ns + 1e6 ps
    red = scopes.reduce(planes)
    # the while is a container: its 10 us are its children's
    assert red["scopes"] == {
        "glm_lbfgs.forward": pytest.approx(4e-6),
        "unscoped": pytest.approx(3e-6)}
    assert red["categories"] == {
        "convolution fusion": pytest.approx(4e-6),
        "a name": pytest.approx(3e-6)}
    assert red["ops_s"] == pytest.approx(7e-6)
    assert red["busy_s"] == pytest.approx(10e-6)      # the loop's interval
    assert red["window_s"] == pytest.approx(20e-6)
    assert red["host_spans"] == {"sst.stage": pytest.approx(1.5e-6)}
    assert red["outside_solver_top"] == [{
        "op": "copy.2:f32[4]", "scope": "unscoped", "source": "",
        "tf_op": "", "s": pytest.approx(3e-6)}]
    assert scopes.solver_seconds(red) == pytest.approx(4e-6)


@pytest.mark.parametrize("tf_op, scope", [
    ("jit(fused_batch)/sst.fit/while/body/glm_lbfgs.linesearch/vmap()/exp:",
     "glm_lbfgs.linesearch"),
    ("jit(f)/sst.fit/glm_lbfgs.init/glm_lbfgs.backward/nbk,nd->bkd/dot:",
     "glm_lbfgs.backward"),
    ("jit(fused_batch)/sst.score/reduce_sum:", "sst.score"),
    ("jit(fused_batch)/sst.fit/while/cond/lt:", "sst.fit"),
    ("jit(step)/dot_general:", "unscoped"),
    ("jit(f)/my_glm_lbfgs.x/add:", "unscoped"),
    ("", "unscoped"), (None, "unscoped")])
def test_scope_is_the_innermost_component(tf_op, scope):
    assert scopes.scope_of(tf_op) == scope


# -- recorded traces ----------------------------------------------------------

def ctx_for(path, tmp_path, monkeypatch):
    """What ``run.run_cell`` hands a reader, for a recorded trace laid
    out as the profiler lays one out."""
    run_dir = tmp_path / "trace" / "plugins" / "profile" / "t0"
    run_dir.mkdir(parents=True)
    shutil.copyfile(path, run_dir / "vm.xplane.pb")
    monkeypatch.setenv("BENCH_TEST_TRACE_DIR", str(tmp_path / "trace"))
    monkeypatch.setattr(scopes, "_PARSED", {})
    return {"trace": trace_reduce.reduce(trace_reduce.load(path), 1),
            "cell": {"name": "a.cell"}, "chips": 1,
            "load_named": lambda spec: run.load_named(spec, BENCH)}


def read_new(ctx):
    return {name: run.load_file(os.path.join(
        BENCH, "layers", name + ".py")).read(ctx) for name in NEW}


def test_trace_with_no_name_of_the_program(tmp_path, monkeypatch, capsys):
    """PR 25's trace: a bare jitted step, no scope and no mirrored span.
    The names XLA gives are read; every new reader says nothing."""
    planes = scopes.read_planes(SMALL)
    device = next(p for p in planes if p["device"] == 0)
    by_op = {name.split(" = ")[0]: stats
             for name, stats in device["events"].values()}
    assert by_op["%fusion"]["tf_op"].rstrip(":") == "jit(step)/dot_general"
    assert by_op["%fusion"]["source"].endswith("small_trace.py:5")
    assert by_op["%copy"]["hlo_category"] == "data formatting"
    ctx = ctx_for(SMALL, tmp_path, monkeypatch)
    red = scopes.read(ctx)
    assert set(red["scopes"]) == {"unscoped"} and not red["host_spans"]
    assert red["busy_s"] == pytest.approx(ctx["trace"]["busy_s"], rel=1e-3)
    assert red["window_s"] == pytest.approx(ctx["trace"]["window_s"])
    assert red["outside_solver_top"][0]["source"].endswith(
        "small_trace.py:6")
    assert read_new(ctx) == dict.fromkeys(NEW)
    out = capsys.readouterr().out
    assert out.count("scopes: {") == 1 and out.count("host spans: {") == 1
    assert "no glm_lbfgs.* scope" in out and "no sst.* host event" in out


@pytest.fixture
def scoped(tmp_path, monkeypatch):
    ctx = ctx_for(SCOPED, tmp_path, monkeypatch)
    return ctx, scopes.read(ctx)


def test_scoped_trace_is_small_and_from_a_tpu():
    assert os.path.getsize(SCOPED) <= 1 << 20
    assert list(trace_reduce.load(SCOPED)["devices"]) == [0]


@pytest.mark.parametrize("scope", SOLVER_SCOPES + ("sst.score",))
def test_scoped_trace_holds_scope(scoped, scope):
    _, red = scoped
    assert red["scopes"].get(scope, 0.0) > 0.0


def test_scoped_trace_sums_reconcile(scoped):
    ctx, red = scoped
    ops = [e for e in ctx["trace"]["ops"]
           if not e[0].startswith(trace_reduce.CONTAINERS)]
    # the same operations, the same window, the same device as the
    # reduction the other metrics read ...
    assert red["ops_s"] == pytest.approx(sum(d for _, _, d in ops), rel=1e-3)
    assert sum(red["scopes"].values()) == pytest.approx(red["ops_s"])
    assert sum(red["categories"].values()) == pytest.approx(red["ops_s"])
    # ... and with the loops' own intervals, the busy time
    assert red["busy_s"] == pytest.approx(ctx["trace"]["busy_s"], rel=1e-3)
    assert red["ops_s"] <= red["busy_s"] * (1 + 1e-9)
    assert scopes.solver_seconds(red) > 0.5 * red["ops_s"]


@pytest.mark.parametrize("span", [
    "sst.stage", "sst.dispatch", "sst.gather", "sst.fit.prepare",
    "sst.fit.plan", "sst.fit.results", "sst.dataplane.upload",
    "sst.dataplane.fingerprint", "sst.device_put.broadcast"])
def test_scoped_trace_holds_host_span(scoped, span):
    _, red = scoped
    assert red["host_spans"].get(span, 0.0) > 0.0
    assert "sst.search.fit" not in red["host_spans"]


def test_readers_return_numbers_on_the_scoped_trace(scoped, capsys):
    ctx, red = scoped
    values = read_new(ctx)
    assert values["solver.device_s"] == \
        pytest.approx(scopes.solver_seconds(red))
    assert 0.0 < values["solver.device_s"] <= ctx["trace"]["busy_s"]
    assert 0.0 < values["solver.linesearch_share"] < 100.0
    assert values["dataplane.upload_s"] == \
        pytest.approx(red["host_spans"]["sst.dataplane.upload"])
    assert 0.0 <= values["search.named_idle_share"] <= 100.0
    # (a search of 13 ms: its gaps are relays of spans of 0.1-1.7 ms, none
    # of which covers half of one, so here the share may well read 0.0)
    assert "inside_fit:sst.search.fit" not in dict(ctx["trace"]["idle_gaps"])


def test_no_device_plane_reads_as_nothing(tmp_path, monkeypatch):
    """XLA:CPU: the trace has the mirrored host spans and no device."""
    import jax
    import jax.numpy as jnp

    trace_dir = tmp_path / "trace"
    with jax.profiler.trace(str(trace_dir)):
        with jax.profiler.TraceAnnotation("bench.search"):
            jax.jit(lambda x: x @ x)(jnp.ones((8, 8))).block_until_ready()
    path = trace_reduce.find_xplane(str(trace_dir))
    assert scopes.reduce(scopes.read_planes(path)) is None
    monkeypatch.setenv("BENCH_TEST_TRACE_DIR", str(trace_dir))
    ctx = {"trace": trace_reduce.reduce(trace_reduce.load(path), 1),
           "cell": {"name": "a.cell"}, "chips": 1,
           "load_named": lambda spec: run.load_named(spec, BENCH)}
    assert ctx["trace"] is None
    assert read_new(ctx) == dict.fromkeys(NEW)


def test_new_metrics_are_appended_entries():
    bench = run.load_json(os.path.join(os.path.dirname(BENCH),
                                       "BENCHMARK.json"))
    assert [m["name"] for m in bench["per_layer"][-4:]] == list(NEW)
    layers = {m["layer"] for m in bench["per_layer"][:-4]}
    assert {m["layer"] for m in bench["per_layer"][-4:]} <= layers
