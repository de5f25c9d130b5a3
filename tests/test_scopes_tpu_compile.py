"""What ``glm_lbfgs_batched`` compiles to on a described TPU v5e.

The solver's launch is compiled for a described (not attached) TPU v5e at
the shape of the benchmark's ``logreg_mnist10k.grid1000`` cell — 10 000
rows x 784 features, ten classes, 625 lanes (125 candidates x 5 folds) —
once as the package has it and once with ``jax.named_scope`` patched to a
null context.  With the debug metadata stripped — each instruction's
``metadata={...}`` and the module's tables of files, functions, locations
and stack frames that it points into — the optimized HLO is the same text;
and the scoped one names every phase in its ``op_name``s.

The line search's compiled shape is pinned at both cells' launch shapes
(``test_linesearch_compiled_shape``): with the multinomial family's
one-pass evaluator no array of ``ls_trials * n * B`` elements is written
and Z is not copied into another layout, and the three other callers of
the solver hand it no evaluator.  Its staged evaluation (the first four
trial steps, the other twelve under a conditional) is one ``conditional``
in the solver loop's body that writes no (t, n, B) rows; the three other
callers lower to the text they lowered to before the staging
(``test_generic_callers_lower_to_the_parents_text``).

The kernel-dual launch (``SVCFamily.fit_task_batched``) is compiled at the
shape of the ``svc_rbf_mnist20k.c4_gamma4`` cell — 20 000 rows x 16
candidates x 5 folds, 45 pairs, ten classes of 2 000 rows, so the duals run
in the class-sorted, block-compact layout: every ``sst.svc.*`` /
``sst.box_fista.*`` scope names device operations, the dual's loop carries
``(225, 2 x 2000)`` iterates and ONE copy of the kernel matrix, and the
memory ledger's price of the launch is within a quarter of what the
compiler allots.  Where the duals keep dense rows (binary problems, skewed
class counts, a compiled Pipeline's per-fold kernels) the launch lowers to
the text of the commit before the layout
(``test_dense_svc_launches_lower_to_the_parents_text``).  Handed the
layout's fact that its 16 candidates are 4 runs of one gamma
(``SVCFamily.launch_layout``), the same launch scans 4 kernels, its loop
carries ``(900, 2 x 2000)`` iterates and still ONE copy of the matrix, and
the ledger prices that too.

The minibatch perceptron's launch (``MLPClassifierFamily.fit`` under the
engine's two ``vmap``s, the candidate axis named) is compiled at the widest
group of the ``mlp_mnist.arch_alpha`` cell — 12 alpha x 5 folds of
``hidden_layer_sizes`` (1000,) on 70 000 x 784: with and without scopes the
same optimized HLO, every ``sst.mlp.*`` scope on a device operation, the
step loop carrying the weights and Adam's two moments and nothing else of
that size, one gather of minibatch rows a fold, and the ledger's price
within a quarter of the compiler's allotment.

The forest's launch (``RandomForestClassifierFamily.fit_task_batched``:
one forest a fold, read at each candidate's count) is compiled at the
deepest group of the
``forest_covtype145k.depth3_trees3`` cell — 3 n_estimators x 5 folds of
max_depth 10 on 145 253 x 54 — with the grower on the kernels' form: both
Mosaic kernels compile for the chip at these widths, every ``sst.tree.*``
scope names device operations, rows are gathered at two levels of ten and
scattered at none, and the ledger's price is within a quarter of the
compiler's allotment.

The boosting launch (``GradientBoostingClassifierFamily.fit_task_batched``
handed ``launch_layout``'s fact, since PR 40) is compiled at the launch of
the ``gbc_covtype145k.lr5_stages3`` cell — its 15 candidates in 5 runs of
one learning rate's 3 counts x 5 folds = 25 lanes of 145 253 x 54, two
classes, each lane one stage loop read at 3 counts — with the grower on
the kernels' form: the real-valued, every-feature path compiles for the
chip (three bfloat16 parts a statistic, ``f32[25, nodes, 64, 8, 256]``
histograms), both ``sst.boost.*`` scopes name device operations, a fold's
rows are sorted and their codes gathered once a launch and no stage sorts
or gathers them, and the ledger's price is within 15 % of the compiler's
allotment.

The neighbour search (``KNeighborsClassifierFamily.fit_task_batched``) is
compiled at the launch of the ``knn_mnist.k30_weights2`` cell —
n_neighbors 1..30 x two weightings x 5 folds on 70 000 x 784, ten classes,
with the row block the ledger's default budget leaves (``launch_tiles``:
two blocks of 35 000 rows): no (70 000, 70 000) array, one top-k of a
block's (35 000, 70 000) distances, the product at three bfloat16 passes,
every ``sst.knn.*`` scope on a device operation, and the ledger's price
within 10 % of the compiler's allotment, over a quarter of the chip.

Nothing runs on a device here and nothing is timed.  The topology is
described inside a fixture (never at import time: only one process may
load the TPU's library), and where it cannot be described the tests skip.
"""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_sklearn_tpu.obs.spans import known_scope_names

N, D, K, LANES, FOLDS = 10_000, 784, 10, 625, 5
METADATA = re.compile(r',? ?metadata=\{(?:[^{}"]|"[^"]*")*\}')
DEBUG_TABLES = re.compile(
    r'^(FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*\n',
    re.M)
SOLVER_SCOPES = sorted(s for s in known_scope_names()
                       if s.startswith("glm_lbfgs."))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled(one_chip, n=N, lanes=LANES):
    """One task-batched LogisticRegression fit launch, compiled."""
    from spark_sklearn_tpu.models.linear import LogisticRegressionFamily

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    meta = {"n_classes": K, "classes": np.arange(K), "n_features": D}
    static = {"max_iter": 100, "__n_folds__": FOLDS, "__bf16__": False}

    def launch(dyn, data, w):
        return LogisticRegressionFamily.fit_task_batched(
            dyn, static, data, w, meta)

    lowered = jax.jit(launch).lower(
        {"C": arg((lanes,))},
        {"X": arg((n, D)), "y": arg((n,), jnp.int32), "y1h": arg((n, K))},
        arg((lanes, n)))
    return lowered.compile()


@pytest.fixture(scope="module")
def hlo_pair(topo, no_compile_cache):
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    scoped = _compiled(one_chip).as_text()
    real = jax.named_scope
    jax.named_scope = lambda name: contextlib.nullcontext()
    try:
        bare = _compiled(one_chip).as_text()
    finally:
        jax.named_scope = real
    return scoped, bare


def _instructions(text):
    return METADATA.sub("", DEBUG_TABLES.sub("", text))


def test_scopes_change_no_instruction(hlo_pair):
    scoped, bare = hlo_pair
    assert "glm_lbfgs." in scoped and "glm_lbfgs." not in bare
    assert _instructions(scoped) == _instructions(bare)
    assert "metadata=" not in _instructions(scoped)
    # the comparison is of whole programs, not of stubs
    assert scoped.count("fusion(") > 20 and "while(" in scoped


@pytest.mark.parametrize("scope", SOLVER_SCOPES)
def test_compiled_op_names_carry_scope(hlo_pair, scope):
    scoped, _ = hlo_pair
    assert re.search(r'op_name="[^"]*/' + re.escape(scope) + r'[/"]', scoped)


# --- the line search's compiled shape --------------------------------------

LS_TRIALS = 16
COMPUTATION = re.compile(r'^(?:ENTRY )?%([\w.\-]+) \(.*\) -> .*\{\s*$')
ARRAY = re.compile(r'\b(?:f32|bf16|s32|u32|pred)\[([\d,]*)\]')


def _instructions_outside_fusions(text):
    """(computation, instruction line) for every instruction that is not
    inside a fusion's body: what such a line produces is a buffer."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = COMPUTATION.match(line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None and " = " in line:
            cur.append(line)
    bodies = set(re.findall(r' fusion\(.*calls=%([\w.\-]+)', text))
    return [(name, line) for name, lines in comps.items()
            if name not in bodies for line in lines]


def _result_elements(line):
    """Element counts of the arrays an instruction line produces."""
    rhs = line.split(" = ", 1)[1]
    if rhs.startswith("("):                   # a tuple of results
        depth = 0
        for end, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        result = rhs[:end + 1]
    else:
        result = rhs.split(" ", 1)[0]
    return [int(np.prod([int(d) for d in dims.split(",") if d]))
            for dims in ARRAY.findall(result)]


def _handed_evaluators(monkeypatch, fit):
    """What ``fit`` hands ``glm_lbfgs_batched`` as ``trial_data_loss``,
    and the fit's result with that evaluator withheld."""
    from spark_sklearn_tpu.ops import solvers
    handed = []
    real = solvers.glm_lbfgs_batched

    def withheld(*args, trial_data_loss=None, **kw):
        handed.append(trial_data_loss)
        return real(*args, **kw)

    monkeypatch.setattr(solvers, "glm_lbfgs_batched", withheld)
    out = fit()
    monkeypatch.setattr(solvers, "glm_lbfgs_batched", real)
    return handed, out


def _other_caller(name):
    """(family, static, n_classes, y dtype) of a caller of the solver
    whose loss has no logsumexp."""
    from spark_sklearn_tpu.models.linear import LogisticRegressionFamily
    from spark_sklearn_tpu.models.svr import LinearSVCFamily, LinearSVRFamily
    return {
        "binary_logreg": (LogisticRegressionFamily, {"max_iter": 100},
                          2, jnp.int32),
        "linear_svc": (LinearSVCFamily, {"max_iter": 1000}, K, jnp.int32),
        "linear_svr": (LinearSVRFamily,
                       {"max_iter": 1000,
                        "loss": "squared_epsilon_insensitive"},
                       0, jnp.float32),
    }[name]


def _lowered_fit(name, n=64, lanes=6):
    """StableHLO text (no locations in it) of one small task-batched fit
    of a caller of the solver, as ``jax.jit(...).lower`` gives it."""
    from spark_sklearn_tpu.models.linear import LogisticRegressionFamily
    if name == "multinomial":
        family, static, n_classes, y_dtype = (
            LogisticRegressionFamily, {"max_iter": 100}, K, jnp.int32)
    else:
        family, static, n_classes, y_dtype = _other_caller(name)
    meta = {"n_classes": n_classes, "classes": np.arange(n_classes),
            "n_features": D}
    data = {"X": jax.ShapeDtypeStruct((n, D), jnp.float32),
            "y": jax.ShapeDtypeStruct((n,), y_dtype),
            "y1h": jax.ShapeDtypeStruct((n, max(n_classes, 1)),
                                        jnp.float32)}
    return jax.jit(lambda dyn, data, w: family.fit_task_batched(
        dyn, static, data, w, meta)).lower(
        {"C": jax.ShapeDtypeStruct((lanes,), jnp.float32)}, data,
        jax.ShapeDtypeStruct((lanes, n), jnp.float32)).as_text()


#: sha256 of ``_lowered_fit(name)`` at the commit before the line search was
#: staged (PR 28, 5401493), on the one supported installation.  A PR that
#: means to change one of these launches replaces its digest.
PARENT_LOWERED_SHA256 = {
    "binary_logreg":
        "c4ec3f648df3c91220d06b440b29cf453eef0be86102ba90614108e6cc716259",
    "linear_svc":
        "4d54bb6c48b6b48979eef07d1cdf6cf4375481bcbb100cbd23d479be87e29f03",
    "linear_svr":
        "9a71cecd6e585f440f5fd16389e27af8e91c685a287dc12efcfe6c011dfc68b3",
}


def _lowered_svc_fit(case, n=64, d=6, folds=3, cands=2):
    """StableHLO text of one small task-batched SVC / NuSVC fit."""
    from spark_sklearn_tpu.models.svm import (
        NuSVCFamily, SVCFamily, _pairs)
    family, counts, fold_inputs = {
        "binary": (SVCFamily, (32, 32), False),
        "skewed": (SVCFamily, (48, 8, 8), False),
        "pipeline": (SVCFamily, (22, 21, 21), True),
        "nu_skewed": (NuSVCFamily, (48, 8, 8), False),
        "balanced": (SVCFamily, (22, 21, 21), False),
    }[case]
    k = len(counts)
    meta = {"n_classes": k, "classes": np.arange(k), "n_features": d,
            "x_var": 1.0, "pairs": _pairs(k), "class_counts": counts}
    static = {"kernel": "rbf", "__n_folds__": folds}
    lanes = cands * folds
    S = jax.ShapeDtypeStruct
    data = {"X": S((n, d), jnp.float32), "y": S((n,), jnp.int32)}
    if fold_inputs:
        data["X_folds"] = S((folds, n, d), jnp.float32)
    return jax.jit(lambda dyn, data, w: family.fit_task_batched(
        dyn, static, data, w, meta)).lower(
        {family.primary_param: S((lanes,), jnp.float32),
         "gamma": S((lanes,), jnp.float32)}, data,
        S((lanes, n), jnp.float32)).as_text()


#: sha256 of ``_lowered_svc_fit(case)`` at the commit before the duals got
#: their block-compact layout (PR 31, 1ed5b2c), on the one supported
#: installation: the launches whose duals keep dense rows.
PARENT_LOWERED_SVC_SHA256 = {
    "binary":
        "8eba3148ce9edb264a13dd662e0c206e72b0929c6663414c4ad50903e3fc8415",
    "skewed":
        "8d46d39753f673d085b016088a0266cc97598c9df86094e0b1f21881f63359a1",
    "pipeline":
        "ebba796f0e382b2c969565f66e7086dcef617fc9b8f6508f9b98318485ea3742",
    "nu_skewed":
        "bb58f67f0857008d5864f2315a176d5bc3423052014850c97eb418b3b02fa53a",
}


@pytest.mark.parametrize("case", sorted(PARENT_LOWERED_SVC_SHA256))
def test_dense_svc_launches_lower_to_the_parents_text(case):
    """Binary problems (one pair holds every row), skewed class counts
    (k blocks of the largest class hold over 1.25 n rows) and a compiled
    Pipeline's per-fold kernels keep dense duals, and their launch is the
    text of the commit before the block-compact layout."""
    import hashlib
    text = _lowered_svc_fit(case)
    assert "stablehlo.sort" not in text          # no class sort
    assert (hashlib.sha256(text.encode()).hexdigest()
            == PARENT_LOWERED_SVC_SHA256[case])


def test_balanced_svc_launch_sorts_rows_by_class():
    """... and the same shapes with balanced classes do not."""
    assert "stablehlo.sort" in _lowered_svc_fit("balanced")


@pytest.mark.parametrize("name", sorted(PARENT_LOWERED_SHA256))
def test_generic_callers_lower_to_the_parents_text(name, monkeypatch):
    """The staged line search is for a caller that hands an evaluator.
    Binary LogisticRegression, LinearSVC and LinearSVR hand none: their
    launch has no conditional, is the same text whatever the staging
    constant, and is the text of the commit before the staging."""
    import hashlib
    from spark_sklearn_tpu.ops import solvers
    text = _lowered_fit(name)
    assert "stablehlo.while" in text
    assert "stablehlo.case" not in text and "stablehlo.if" not in text
    monkeypatch.setattr(solvers, "_LS_FIRST_STAGE", 7)
    assert _lowered_fit(name) == text
    assert (hashlib.sha256(text.encode()).hexdigest()
            == PARENT_LOWERED_SHA256[name])


def test_multinomial_launch_lowers_to_one_conditional():
    """Both stages, the conditional and the pick under the one scope the
    benchmark reads; the counter in the loop's state."""
    text = _lowered_fit("multinomial")
    assert text.count("stablehlo.case") + text.count("stablehlo.if") == 1


@pytest.mark.parametrize("case", [
    ("multinomial", 10_000, 625), ("multinomial", 70_000, 190),
    ("binary_logreg",), ("linear_svc",), ("linear_svr",)],
    ids=lambda c: "-".join(str(p) for p in c))
def test_linesearch_compiled_shape(case, request, monkeypatch):
    if case[0] != "multinomial":
        # no logsumexp in the loss, no class axis to unroll: the solver is
        # handed no evaluator and its line search is the parent's, to the
        # instruction (the launch's optimized HLO for a described v5e was
        # diffed against the parent commit's once: CHANGES.md, PR 27)
        family, static, n_classes, y_dtype = _other_caller(case[0])
        n, lanes = 64, 6
        meta = {"n_classes": n_classes, "classes": np.arange(n_classes),
                "n_features": D}
        data = {"X": jax.ShapeDtypeStruct((n, D), jnp.float32),
                "y": jax.ShapeDtypeStruct((n,), y_dtype),
                "y1h": jax.ShapeDtypeStruct((n, max(n_classes, 1)),
                                            jnp.float32)}
        handed, _ = _handed_evaluators(monkeypatch, lambda: jax.eval_shape(
            lambda dyn, data, w: family.fit_task_batched(
                dyn, static, data, w, meta),
            {"C": jax.ShapeDtypeStruct((lanes,), jnp.float32)}, data,
            jax.ShapeDtypeStruct((lanes, n), jnp.float32)))
        assert handed == [None]
        hook = getattr(family, "linesearch_one_pass", None)
        assert hook is None or not hook(static, meta)
        return

    from jax.sharding import SingleDeviceSharding
    request.getfixturevalue("no_compile_cache")
    one_chip = SingleDeviceSharding(request.getfixturevalue("topo").devices[0])
    _, n, lanes = case
    one_pass = _compiled(one_chip, n, lanes)
    handed, generic = _handed_evaluators(
        monkeypatch, lambda: _compiled(one_chip, n, lanes))
    assert len(handed) == 1 and handed[0] is not None

    def buffers(compiled):
        """(lines that write an array of ls_trials*n*B elements or more,
        copies of an f32[n,B,k] array inside the while loop's body)."""
        outside = _instructions_outside_fusions(compiled.as_text())
        trial_tensors = [line for _, line in outside
                         if max(_result_elements(line), default=0)
                         >= LS_TRIALS * n * lanes]
        relayouts = [line for comp, line in outside
                     if "region" in comp and re.search(r' copy(-start)?\(', line)
                     and re.search(r'= \(?f32\[%d,%d,%d\]' % (n, lanes, K),
                                   line)]
        return trial_tensors, relayouts

    # the generic path shows that the test can see both
    trial_tensors, relayouts = buffers(generic)
    assert len(trial_tensors) >= 3 and len(relayouts) == 2
    assert buffers(one_pass) == ([], [])
    # the staged search: ONE conditional, in the solver loop's own body,
    # and neither stage writes its trials' (t, n, B) rows out
    text = one_pass.as_text()
    outside = _instructions_outside_fusions(text)
    conditionals = [comp for comp, line in outside
                    if " conditional(" in line]
    loop_bodies = re.findall(r' while\(.*body=%([\w.\-]+)', text)
    assert len(conditionals) == 1 and conditionals[0] in loop_bodies
    assert " conditional(" not in generic.as_text()
    stage_rows = re.compile(
        r'f32\[(?:4|12|16),%d,%d\]|f32\[%d,%d,(?:4|12|16)\]'
        % (n, lanes, n, lanes))
    assert not [line for _, line in outside
                if stage_rows.search(line.split(" = ", 1)[1].split("(")[0])]
    assert (one_pass.memory_analysis().temp_size_in_bytes
            < 0.6 * generic.memory_analysis().temp_size_in_bytes)


# --- the kernel-dual launch --------------------------------------------------

SVC_N, SVC_CANDIDATES = 20_000, 16
SVC_SCOPES = sorted(s for s in known_scope_names()
                    if s.startswith(("sst.svc.", "sst.box_fista.")))


def _compiled_svc(topo, static):
    """One task-batched SVC(rbf) fit launch at the cell's shape, compiled."""
    from jax.sharding import SingleDeviceSharding
    from spark_sklearn_tpu.models.svm import SVCFamily, _pairs
    one_chip = SingleDeviceSharding(topo.devices[0])

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    meta = {"n_classes": K, "classes": np.arange(K), "n_features": D,
            "x_var": 0.0857, "pairs": _pairs(K),
            "class_counts": (SVC_N // K,) * K}
    static = {"kernel": "rbf", "__n_folds__": FOLDS, **static}
    lanes = SVC_CANDIDATES * FOLDS
    compiled = jax.jit(
        lambda dyn, data, w: SVCFamily.fit_task_batched(
            dyn, static, data, w, meta)).lower(
        {"C": arg((lanes,)), "gamma": arg((lanes,))},
        {"X": arg((SVC_N, D)), "y": arg((SVC_N,), jnp.int32)},
        arg((lanes, SVC_N))).compile()
    return compiled, meta


@pytest.fixture(scope="module")
def svc_launch(topo, no_compile_cache):
    return _compiled_svc(topo, {})


#: what ``SVCFamily.launch_layout`` hands the cell's launch: 4 C a gamma
SVC_RUN = {"__kernel_run__": 4}


@pytest.fixture(scope="module")
def svc_grouped_launch(topo, no_compile_cache):
    return _compiled_svc(topo, SVC_RUN)


def test_svc_scopes_are_the_vocabularys():
    assert SVC_SCOPES == [
        "sst.box_fista.gradient", "sst.box_fista.momentum",
        "sst.box_fista.project", "sst.svc.compact", "sst.svc.decision",
        "sst.svc.gram", "sst.svc.intercept", "sst.svc.power_step"]


@pytest.mark.parametrize("scope", SVC_SCOPES)
def test_svc_compiled_op_names_carry_scope(svc_launch, scope):
    text = svc_launch[0].as_text()
    assert re.search(r'op_name="[^"]*/' + re.escape(scope) + r'[/"]', text)


def test_svc_dual_loop_carries_one_kernel_matrix(svc_launch):
    """The loop of ``_box_fista`` (of the loops whose state holds the
    duals' iterates the one with a matrix) carries the kernel matrix once
    — the bfloat16 copy its product reads, as the (class, row of the
    class, column) view the product by class contracts — and no float32
    matrix beside it: an iteration then reads 0.8 GB, not 2.4 GB.  The
    iterates are block-compact: (225, 2 x 2000), and no loop of the
    launch carries a (225, 20000) array."""
    text = svc_launch[0].as_text()
    n_b = SVC_N // K
    n_p = K * n_b
    iterate = "f32[%d,%d]" % (FOLDS * 45, 2 * n_b)
    states = [line.split(" while(")[0] for line in text.splitlines()
              if " while(" in line]
    loops = [state for state in states if iterate in state]
    matrix = re.compile(r'\b(f32|bf16)\[(?:%d,%d|%d,%d,%d)\]'
                        % (n_p, n_p, K, n_b, n_p))
    carried = sorted(matrix.findall(loop) for loop in loops)
    # the projection's bisection (nested, no matrix) and the dual's loop
    assert carried == [[], ["bf16"]]
    assert not [state for state in states
                if "f32[%d,%d]" % (FOLDS * 45, SVC_N) in state]


def test_svc_grouped_launch_reads_one_matrix_for_four_candidates(
        svc_grouped_launch):
    """The candidates of one gamma stacked: the dual's loop carries
    (4 x 225, 2 x 2000) iterates and ONE bfloat16 matrix, its product by
    class is (10, 200, 2000) against (10, 2000, 20000) — one read for four
    candidates — and the scan takes 4 steps of 4 candidates x 5 folds."""
    text = svc_grouped_launch[0].as_text()
    n_b = SVC_N // K
    n_p = K * n_b
    run = SVC_RUN["__kernel_run__"]
    iterate = "f32[%d,%d]" % (run * FOLDS * 45, 2 * n_b)
    states = [line.split(" while(")[0] for line in text.splitlines()
              if " while(" in line]
    loops = [state for state in states if iterate in state]
    matrix = re.compile(r'\b(f32|bf16)\[(?:%d,%d|%d,%d,%d)\]'
                        % (n_p, n_p, K, n_b, n_p))
    # the projection's bisection (nested, no matrix) and the dual's loop
    assert sorted(matrix.findall(loop) for loop in loops) == [[], ["bf16"]]
    assert not [state for state in states
                if "f32[%d,%d]" % (FOLDS * 45, 2 * n_b) in state]
    assert "f32[%d,%d,%d]" % (K, run * K * FOLDS, n_p) in text
    assert "f32[%d,%d,%d]" % (K, K * FOLDS, n_p) not in text
    stacked = "f32[%d,%d,%d,45]" % (
        SVC_CANDIDATES // run, run * FOLDS, SVC_N)
    assert [state for state in states if stacked in state]
    assert "f32[%d,%d,%d,45]" % (SVC_CANDIDATES, FOLDS, SVC_N) not in text
    # the decisions go back into the caller's row order a candidate at a
    # time: ONE gather of the stacked (900, 20000) rows read NaN and other
    # rows' values on the chip (PERF.md, PR 34)
    gathers = re.findall(r'= f32\[(\d+),%d\]\S* gather\(' % SVC_N, text)
    assert gathers.count(str(FOLDS * 45)) == run
    assert str(run * FOLDS * 45) not in gathers


@pytest.mark.parametrize("launch", ["svc_launch", "svc_grouped_launch"])
def test_ledger_prices_the_svc_launch(launch, request):
    """``SVCFamily.launch_workspace`` (what ``search_report["memory"]``
    models a launch at) against the compiler's own allotment, a kernel a
    candidate and a kernel for the four candidates of a gamma."""
    from spark_sklearn_tpu.models.svm import SVCFamily
    from spark_sklearn_tpu.parallel.memledger import model_group_footprint
    compiled, meta = request.getfixturevalue(launch)
    static = SVC_RUN if launch == "svc_grouped_launch" else {}
    stats = compiled.memory_analysis()
    allotted = (stats.temp_size_in_bytes + stats.argument_size_in_bytes
                + stats.output_size_in_bytes)
    lanes = SVC_CANDIDATES * FOLDS
    modeled = model_group_footprint(
        {"C": np.zeros(SVC_CANDIDATES, np.float32),
         "gamma": np.zeros(SVC_CANDIDATES, np.float32)},
        SVC_CANDIDATES, FOLDS, task_batched=True, n_samples=SVC_N,
        workspace=SVCFamily.launch_workspace(SVC_N, meta, FOLDS,
                                             static=static))
    resident = SVC_N * D * 4        # X, broadcast once
    assert lanes * SVC_N * 4 == modeled["mask_bytes"]
    assert abs(modeled["chunk_bytes"] + resident - allotted) \
        < 0.25 * allotted
    # a fifth of the chip and more: the cell's size (PERF.md section 4;
    # over a quarter until the block-compact layout dropped the matrix's
    # third copy and four fifths of the duals' columns)
    assert allotted > 0.2 * 16.909e9


# --- the minibatch perceptron's launch ---------------------------------------

MLP_N, MLP_CANDIDATES, MLP_HIDDEN, MLP_BATCH = 70_000, 12, [1000], 200
MLP_SCOPES = sorted(s for s in known_scope_names()
                    if s.startswith("sst.mlp."))


def _compiled_mlp(one_chip):
    """The per-task MLPClassifier fit launch as the engine vmaps it
    (candidates, named; folds over the shared-prefix stage's per-fold
    rows) at the widest group of ``mlp_mnist.arch_alpha``: 12 alpha x 5
    folds of hidden_layer_sizes (1000,) on 70 000 x 784."""
    from spark_sklearn_tpu.models.base import CANDIDATE_AXIS
    from spark_sklearn_tpu.models.mlp import MLPClassifierFamily

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    meta = {"n_classes": K, "classes": np.arange(K), "n_features": D}
    static = {"hidden_layer_sizes": MLP_HIDDEN, "max_iter": 8,
              "random_state": 0}

    def launch(alpha, X_folds, y, y1h, w):
        def one_cand(a):
            def one_fold(wf, Xf):
                return MLPClassifierFamily.fit(
                    {"alpha": a}, static, {"X": Xf, "y": y, "y1h": y1h},
                    wf, meta)
            return jax.vmap(one_fold)(w, X_folds)
        with jax.named_scope("sst.fit"):
            return jax.vmap(one_cand, axis_name=CANDIDATE_AXIS)(alpha)

    return jax.jit(launch).lower(
        arg((MLP_CANDIDATES,)), arg((FOLDS, MLP_N, D)),
        arg((MLP_N,), jnp.int32), arg((MLP_N, K)),
        arg((FOLDS, MLP_N))).compile(), meta, static


@pytest.fixture(scope="module")
def mlp_pair(topo, no_compile_cache):
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    scoped, meta, static = _compiled_mlp(one_chip)
    real = jax.named_scope
    jax.named_scope = lambda name: contextlib.nullcontext()
    try:
        bare = _compiled_mlp(one_chip)[0].as_text()
    finally:
        jax.named_scope = real
    return scoped, bare, meta, static


def test_mlp_scopes_are_the_vocabularys():
    assert MLP_SCOPES == [
        "sst.mlp.backward", "sst.mlp.epoch", "sst.mlp.forward",
        "sst.mlp.gather", "sst.mlp.update"]


def test_mlp_scopes_change_no_instruction(mlp_pair):
    scoped, bare = mlp_pair[0].as_text(), mlp_pair[1]
    assert "sst.mlp." in scoped and "sst.mlp." not in bare
    assert _instructions(scoped) == _instructions(bare)
    assert scoped.count("fusion(") > 20 and scoped.count(" while(") >= 2


@pytest.mark.parametrize("scope", MLP_SCOPES)
def test_mlp_compiled_op_names_carry_scope(mlp_pair, scope):
    text = mlp_pair[0].as_text()
    assert re.search(r'op_name="[^"]*/' + re.escape(scope) + r'[/"]', text)


def test_mlp_step_gathers_once_a_fold_and_carries_three_copies(mlp_pair):
    """The step loop's state: the first layer's weights and Adam's two
    moments, (folds, candidates, 784, 1000) each, and no fourth array of
    that size (no best weights without early_stopping, no gradient kept
    from step to step).  The minibatch is gathered once a fold — 5 x 200
    rows — never once a lane."""
    text = mlp_pair[0].as_text()
    first = re.compile(r'f32\[(?:%d,%d|%d,%d),%d,%d\]' % (
        FOLDS, MLP_CANDIDATES, MLP_CANDIDATES, FOLDS, D, MLP_HIDDEN[0]))
    states = [line.split(" while(")[0] for line in text.splitlines()
              if " while(" in line]
    assert max(len(first.findall(state)) for state in states) == 3
    lanes = MLP_CANDIDATES * FOLDS
    assert re.search(r'\[%d,%d\]' % (FOLDS * MLP_BATCH, D), text)
    assert not re.search(r'\[(?:%d,%d,%d|%d),%d\]' % (
        MLP_CANDIDATES, FOLDS, MLP_BATCH, lanes * MLP_BATCH, D), text)


def test_ledger_prices_the_mlp_launch(mlp_pair):
    """``MLPClassifierFamily.launch_workspace`` through the Pipeline's
    (what ``search_report["memory"]`` models the cell's widest group at)
    against the compiler's own allotment."""
    from spark_sklearn_tpu.models.mlp import MLPClassifierFamily
    from spark_sklearn_tpu.models.pipeline import PipelineFamily
    from spark_sklearn_tpu.models.preprocessing import STEP_REGISTRY
    from spark_sklearn_tpu.parallel.memledger import model_group_footprint
    compiled, _, meta, static = mlp_pair
    stats = compiled.memory_analysis()
    allotted = (stats.temp_size_in_bytes + stats.argument_size_in_bytes
                + stats.output_size_in_bytes)
    family = PipelineFamily([("scale", STEP_REGISTRY["StandardScaler"])],
                            "mlp", MLPClassifierFamily)
    modeled = model_group_footprint(
        {"mlp__alpha": np.zeros(MLP_CANDIDATES, np.float32)},
        MLP_CANDIDATES, FOLDS, task_batched=False, n_samples=MLP_N,
        workspace=family.launch_workspace(
            MLP_N, meta, FOLDS,
            static={f"mlp__{k}": v for k, v in static.items()}))
    # a lane's weights are megabytes: 795 010 parameters, 8 copies
    assert modeled["per_candidate_bytes"] > FOLDS * 8 * 795_010 * 4
    assert abs(modeled["chunk_bytes"] - allotted) < 0.25 * allotted
    # an eighth of the chip and more: the cell's size (PERF.md section 4)
    assert allotted > 0.125 * 16.909e9


# --- the forest's launch -----------------------------------------------------

TREE_N, TREE_D, TREE_K, TREE_CANDIDATES, TREE_DEPTH = 145_253, 54, 7, 3, 10
TREE_SCOPES = sorted(s for s in known_scope_names()
                     if s.startswith("sst.tree."))


def _compiled_forest(one_chip):
    """The RandomForestClassifier fit launch as the engine builds it
    (``fit_task_batched`` on the candidate-major tasks and their tiled
    masks) at the deepest group of ``forest_covtype145k.depth3_trees3``:
    3 n_estimators x 5 folds of max_depth 10 on 145 253 x 54, seven
    classes, the grower on the kernels' form (the platform's choice on a
    TPU)."""
    from spark_sklearn_tpu.models.trees import RandomForestClassifierFamily

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    meta = {"n_classes": TREE_K, "classes": np.arange(TREE_K),
            "n_features": TREE_D, "max_estimators": 40,
            "unit_fit_weights": True}
    static = {"max_depth": TREE_DEPTH, "random_state": 0}
    tasks = TREE_CANDIDATES * FOLDS

    def launch(n_estimators, codes, y, y1h, w):
        with jax.named_scope("sst.fit"):
            return RandomForestClassifierFamily.fit_task_batched(
                {"n_estimators": n_estimators},
                {**static, "__n_folds__": FOLDS},
                {"codes": codes, "y": y, "y1h": y1h}, w, meta)

    return jax.jit(launch).lower(
        arg((tasks,), jnp.int32),
        arg((TREE_N, TREE_D), jnp.uint8), arg((TREE_N,), jnp.int32),
        arg((TREE_N, TREE_K)), arg((tasks, TREE_N))).compile(), meta, static


@pytest.fixture(scope="module")
def forest_launch(topo, no_compile_cache):
    from jax.sharding import SingleDeviceSharding
    from spark_sklearn_tpu.ops import tree_hist
    real = tree_hist.on_tpu
    tree_hist.on_tpu = lambda: True     # the platform compiled for
    try:
        return _compiled_forest(SingleDeviceSharding(topo.devices[0]))
    finally:
        tree_hist.on_tpu = real


def test_tree_scopes_are_the_vocabularys():
    assert TREE_SCOPES == [
        "sst.tree.bootstrap", "sst.tree.histogram", "sst.tree.partition",
        "sst.tree.predict", "sst.tree.route", "sst.tree.split"]


@pytest.mark.parametrize("scope", TREE_SCOPES)
def test_tree_compiled_op_names_carry_scope(forest_launch, scope):
    text = forest_launch[0].as_text()
    assert re.search(r'op_name="[^"]*/' + re.escape(scope) + r'[/"]', text)


def test_forest_launch_is_two_kernels_a_level_and_two_sorts_a_tree(
        forest_launch):
    """The Mosaic kernels compile for the chip at the cell's widths (a
    histogram and a routing call a level, the 5 forests of the 15 tasks a
    grid axis of each; a level's histograms hold each node's own 7
    features, in 8 slots, and none of the 64 padded features is built),
    the rows are gathered into node order at levels 0 and 5 only, and no
    level scatters."""
    text = forest_launch[0].as_text()
    calls = re.findall(r'custom_call_target="tpu_custom_call"', text)
    assert len(calls) == 2 * TREE_DEPTH
    lanes = FOLDS
    for level in (0, TREE_DEPTH - 1):
        assert re.search(r'f32\[%d,%d,8,8,256\]\S* custom-call\(' % (
            lanes, 2 ** level), text)
    assert not re.search(r'f32\[%d,\d+,64,8,256\]' % lanes, text)
    assert " scatter(" not in text
    gathered = re.findall(r'= s32\[%d,(\d+)\]\S* gather\(' % (
        lanes * TREE_N), text)
    # the codes' 16 words and the statistics' 4, at each of the two sorts,
    # every lane's rows by one gather of one table
    assert sorted(gathered) == ["16", "16", "4", "4"]


def test_ledger_prices_the_forest_launch(forest_launch):
    """``RandomForestClassifierFamily.launch_workspace`` (what
    ``search_report["memory"]`` models the cell's deepest group at)
    against the compiler's own allotment."""
    from spark_sklearn_tpu.models.trees import RandomForestClassifierFamily
    from spark_sklearn_tpu.ops import tree_hist
    from spark_sklearn_tpu.parallel.memledger import model_group_footprint
    compiled, meta, static = forest_launch
    stats = compiled.memory_analysis()
    allotted = (stats.temp_size_in_bytes + stats.argument_size_in_bytes
                + stats.output_size_in_bytes)
    real = tree_hist.on_tpu
    tree_hist.on_tpu = lambda: True
    try:
        workspace = RandomForestClassifierFamily.launch_workspace(
            TREE_N, meta, FOLDS, static=static)
    finally:
        tree_hist.on_tpu = real
    modeled = model_group_footprint(
        {"n_estimators": np.zeros(TREE_CANDIDATES, np.int32)},
        TREE_CANDIDATES, FOLDS, task_batched=True, n_samples=TREE_N,
        workspace=workspace)
    # a forest's histograms are its nodes' own 7 features: 512 nodes x 8
    # slots x 8 x 256 floats at level 9, 33.5 MB where every feature's
    # were 268 MB, so what a forest holds is what the sorts hold by row;
    # a forest a fold whatever the candidates, whose own share is their
    # masks and votes
    assert modeled["fixed_bytes"] > FOLDS * 1.39 * 33_554_432
    assert modeled["fixed_bytes"] < FOLDS * 268_435_456
    assert modeled["per_candidate_bytes"] < 0.2 * modeled["fixed_bytes"]
    assert abs(modeled["chunk_bytes"] - allotted) < 0.1 * allotted
    # under a GB where every feature's histograms held 2.13 GB (PERF.md
    # section 4)
    assert allotted < 1e9


# --- the boosting launch -----------------------------------------------------

BOOST_CANDIDATES, BOOST_RUN, BOOST_DEPTH = 15, 3, 3
BOOST_LANES = BOOST_CANDIDATES // BOOST_RUN * FOLDS
BOOST_SCOPES = sorted(s for s in known_scope_names()
                      if s.startswith("sst.boost."))


def _compiled_boost(one_chip):
    """The GradientBoostingClassifier fit launch as the engine builds it
    for ``gbc_covtype145k.lr5_stages3`` (``fit_task_batched`` on the
    candidate-major tasks and their tiled masks, with the layout's fact):
    5 learning rates x 3 n_estimators x 5 folds on 145 253 x 54, two
    classes, the grower on the kernels' form."""
    from spark_sklearn_tpu.models.trees import (
        _STAGE_RUN, GradientBoostingClassifierFamily)

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    meta = {"n_classes": 2, "classes": np.arange(2), "n_features": TREE_D,
            "max_estimators": 100}
    static = {"random_state": 0, _STAGE_RUN: BOOST_RUN}
    tasks = BOOST_CANDIDATES * FOLDS

    def launch(dyn, codes, y, y1h, train_w):
        data = {"codes": codes, "y": y, "y1h": y1h}
        with jax.named_scope("sst.fit"):
            return GradientBoostingClassifierFamily.fit_task_batched(
                dyn, {**static, "__n_folds__": FOLDS}, data, train_w, meta)

    return jax.jit(launch).lower(
        {"learning_rate": arg((tasks,)),
         "n_estimators": arg((tasks,), jnp.int32)},
        arg((TREE_N, TREE_D), jnp.uint8), arg((TREE_N,), jnp.int32),
        arg((TREE_N, 2)), arg((tasks, TREE_N))).compile(), meta, static


@pytest.fixture(scope="module")
def boost_launch(topo, no_compile_cache):
    from jax.sharding import SingleDeviceSharding
    from spark_sklearn_tpu.ops import tree_hist
    real = tree_hist.on_tpu
    tree_hist.on_tpu = lambda: True     # the platform compiled for
    try:
        return _compiled_boost(SingleDeviceSharding(topo.devices[0]))
    finally:
        tree_hist.on_tpu = real


def test_boost_scopes_are_the_vocabularys():
    assert BOOST_SCOPES == ["sst.boost.gradient", "sst.boost.update"]


@pytest.mark.parametrize("scope", BOOST_SCOPES + [
    "sst.tree.histogram", "sst.tree.partition", "sst.tree.predict"])
def test_boost_compiled_op_names_carry_scope(boost_launch, scope):
    text = boost_launch[0].as_text()
    assert re.search(r'op_name="[^"]*/' + re.escape(scope) + r'[/"]', text)


def test_boost_launch_is_every_features_histograms_in_three_parts(
        boost_launch):
    """The path no forest takes since PR 38: a slot is a feature (64 with
    the kernel's padding) at every node of the three levels; a row's
    statistics are laid out as three bfloat16 parts of 8 statistic rows;
    the rows of a fold are sorted, and their codes' 16 words gathered,
    once a launch (a fold's 5 runs share its order), and no stage sorts
    or gathers codes or statistics; no level scatters.  The 25 lanes are
    5 folds of code tiles a grid axis, each tile shared by its 5 runs:
    the kernels' outputs are ``(runs, folds, 1, ...)`` and no fold's
    codes are broadcast to its runs."""
    text = boost_launch[0].as_text()
    runs = BOOST_CANDIDATES // BOOST_RUN
    calls = re.findall(r'custom_call_target="tpu_custom_call"', text)
    assert len(calls) == 2 * BOOST_DEPTH
    for level in range(BOOST_DEPTH):
        assert re.search(r'f32\[%d,%d,1,%d,64,8,256\]\S* custom-call\(' % (
            runs, FOLDS, 2 ** level), text)
    assert not re.search(r'u8\[%d,%d,64,\d+\]' % (runs, FOLDS), text)
    assert " scatter(" not in text
    gathered = [(rows, words) for rows, words in re.findall(
        r'= s32\[(\d+),(\d+)\]\S* gather\(', text) if int(rows) >= TREE_N]
    assert gathered == [(str(FOLDS * TREE_N), "16")]
    sorted_rows = re.findall(r'= \((s32\[[\d,]+\])[^=]*\) sort\(', text)
    assert sorted_rows == ["s32[%d,%d]" % (FOLDS, TREE_N)]
    assert re.search(r'bf16\[%d,%d,1,24,\d+\]' % (runs, FOLDS), text)


def test_ledger_prices_the_boost_launch(boost_launch):
    """``GradientBoostingClassifierFamily.launch_workspace`` (what
    ``search_report["memory"]`` models the cell's group at) against the
    compiler's own allotment."""
    from spark_sklearn_tpu.models.trees import (
        GradientBoostingClassifierFamily)
    from spark_sklearn_tpu.ops import tree_hist
    from spark_sklearn_tpu.parallel.memledger import model_group_footprint
    compiled, meta, static = boost_launch
    stats = compiled.memory_analysis()
    allotted = (stats.temp_size_in_bytes + stats.argument_size_in_bytes
                + stats.output_size_in_bytes)
    real = tree_hist.on_tpu
    tree_hist.on_tpu = lambda: True
    try:
        workspace = GradientBoostingClassifierFamily.launch_workspace(
            TREE_N, meta, FOLDS, static=static)
    finally:
        tree_hist.on_tpu = real
    modeled = model_group_footprint(
        {"learning_rate": np.zeros(BOOST_CANDIDATES, np.float32),
         "n_estimators": np.zeros(BOOST_CANDIDATES, np.int32)},
        BOOST_CANDIDATES, FOLDS, task_batched=True, n_samples=TREE_N,
        workspace=workspace)
    # a lane is one copy of its rows' kernels' bytes but their codes,
    # 17.4 MB, shared by a run's 3 candidates; a fold's order, 19.8 MB,
    # and its code tiles are the launch's
    assert modeled["fixed_bytes"] == FOLDS * TREE_N * 136
    assert modeled["per_candidate_bytes"] > FOLDS * 17e6 / BOOST_RUN
    assert abs(modeled["chunk_bytes"] - allotted) < 0.15 * allotted
    # 2.72 GB where every stage sorted and gathered each lane's rows
    # (PERF.md section 4)
    assert allotted < 1.3e9


# --- the neighbour search ----------------------------------------------------

KNN_N, KNN_CANDIDATES, KNN_MAX_K = 70_000, 60, 30
KNN_SCOPES = sorted(s for s in known_scope_names() if s.startswith("sst.knn."))
#: the cell's budget: the ledger's default fraction of a v5e's 16.909 GB
KNN_BUDGET = int(0.8 * 16.909e9)


def _compiled_knn(one_chip):
    """The KNeighborsClassifier fit launch as the engine builds it for
    ``knn_mnist.k30_weights2``: n_neighbors 1..30 x two weightings x 5
    folds on 70 000 x 784, ten classes, with the row block the ledger's
    budget leaves (``launch_tiles``), followed by the prediction the
    scorer reads."""
    from spark_sklearn_tpu.models.neighbors import KNeighborsClassifierFamily

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    meta = {"n_classes": K, "classes": np.arange(K), "n_features": D,
            "n_samples": KNN_N, "max_k": KNN_MAX_K, "held_out_once": True}
    tasks = KNN_CANDIDATES * FOLDS
    resident = KNN_N * D * 4 + KNN_N * 4 + 5 * FOLDS * KNN_N * 4
    static = {"weights": "uniform", **KNeighborsClassifierFamily.launch_tiles(
        {}, meta, FOLDS, KNN_CANDIDATES, n_samples=KNN_N,
        budget_bytes=KNN_BUDGET, resident_bytes=resident,
        return_train=False)}

    def launch(dyn, X, y, train_w):
        with jax.named_scope("sst.fit"):
            model = KNeighborsClassifierFamily.fit_task_batched(
                dyn, {**static, "__n_folds__": FOLDS}, {"X": X, "y": y},
                train_w, meta)
        return jnp.argmax(model["proba"], axis=-1)

    return jax.jit(launch).lower(
        {"n_neighbors": arg((tasks,), jnp.int32),
         "weights": arg((tasks,), jnp.int32)},
        arg((KNN_N, D)), arg((KNN_N,), jnp.int32),
        arg((tasks, KNN_N))).compile(), meta, static


@pytest.fixture(scope="module")
def knn_launch(topo, no_compile_cache):
    from jax.sharding import SingleDeviceSharding
    return _compiled_knn(SingleDeviceSharding(topo.devices[0]))


def test_knn_scopes_are_the_vocabularys():
    assert KNN_SCOPES == ["sst.knn.distance", "sst.knn.select",
                          "sst.knn.vote"]


@pytest.mark.parametrize("scope", KNN_SCOPES)
def test_knn_compiled_op_names_carry_scope(knn_launch, scope):
    text = knn_launch[0].as_text()
    assert re.search(r'op_name="[^"]*/' + re.escape(scope) + r'[/"]', text)


def test_knn_launch_is_row_blocks_and_one_selection(knn_launch):
    """Two blocks of 35 000 rows against every row: no (n, n) array, one
    top-k of (35 000, 70 000) a block, and the product at three
    bfloat16 passes."""
    from spark_sklearn_tpu.models.neighbors import _BLOCK_ROWS
    compiled, _, static = knn_launch
    text = compiled.as_text()
    R = static[_BLOCK_ROWS]
    assert R == 35_000
    assert not re.search(r"\[%d,%d\]" % (KNN_N, KNN_N), text)
    assert re.search(r"f32\[%d,%d\]" % (R, KNN_N), text)
    assert len(re.findall(r"TopK", text)) == 1
    assert "algorithm=dot_bf16_bf16_f32_x3" in text \
        or "operand_precision={high,high}" in text


def test_ledger_prices_the_knn_launch(knn_launch):
    """``KNeighborsClassifierFamily.launch_workspace`` (what
    ``search_report["memory"]`` models the cell's group at) against the
    compiler's own allotment, and the cell's size."""
    from spark_sklearn_tpu.models.neighbors import KNeighborsClassifierFamily
    from spark_sklearn_tpu.parallel.memledger import model_group_footprint
    compiled, meta, static = knn_launch
    stats = compiled.memory_analysis()
    allotted = (stats.temp_size_in_bytes + stats.argument_size_in_bytes
                + stats.output_size_in_bytes)
    workspace = KNeighborsClassifierFamily.launch_workspace(
        KNN_N, meta, FOLDS, static=static)
    modeled = model_group_footprint(
        {"n_neighbors": np.zeros(KNN_CANDIDATES, np.int32),
         "weights": np.zeros(KNN_CANDIDATES, np.int32)},
        KNN_CANDIDATES, FOLDS, task_batched=True, n_samples=KNN_N,
        workspace=workspace)
    assert abs(modeled["chunk_bytes"] + KNN_N * D * 4 - allotted) \
        < 0.1 * allotted
    # a quarter of the chip and more: the cell's size (PERF.md section 4)
    assert allotted > 0.25 * 16.909e9
