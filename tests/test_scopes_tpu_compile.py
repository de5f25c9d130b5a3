"""The named scopes of ``glm_lbfgs_batched`` change no instruction.

The solver's launch is compiled for a described (not attached) TPU v5e at
the shape of the benchmark's ``logreg_mnist10k.grid1000`` cell — 10 000
rows x 784 features, ten classes, 625 lanes (125 candidates x 5 folds) —
once as the package has it and once with ``jax.named_scope`` patched to a
null context.  With the debug metadata stripped — each instruction's
``metadata={...}`` and the module's tables of files, functions, locations
and stack frames that it points into — the optimized HLO is the same text;
and the scoped one names every phase in its ``op_name``s.

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


def _compiled_text(one_chip):
    """Optimized HLO of one task-batched LogisticRegression fit launch."""
    from spark_sklearn_tpu.models.linear import LogisticRegressionFamily

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    meta = {"n_classes": K, "classes": np.arange(K), "n_features": D}
    static = {"max_iter": 100, "__n_folds__": FOLDS, "__bf16__": False}

    def launch(dyn, data, w):
        return LogisticRegressionFamily.fit_task_batched(
            dyn, static, data, w, meta)

    lowered = jax.jit(launch).lower(
        {"C": arg((LANES,))},
        {"X": arg((N, D)), "y": arg((N,), jnp.int32), "y1h": arg((N, K))},
        arg((LANES, N)))
    return lowered.compile().as_text()


@pytest.fixture(scope="module")
def hlo_pair(topo, no_compile_cache):
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    scoped = _compiled_text(one_chip)
    real = jax.named_scope
    jax.named_scope = lambda name: contextlib.nullcontext()
    try:
        bare = _compiled_text(one_chip)
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
