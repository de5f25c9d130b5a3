"""Pipelined chunk executor (parallel/pipeline.py + grid._run_groups).

The contract under test: pipelining reorders HOST work only — staging,
gather, compile — so `cv_results_` must be EXACT-equal (not tolerance)
between `pipeline_depth=0` (the synchronous escape hatch) and the
pipelined default, across compiled families, multimetric scoring,
error_score masking, and checkpoint-resume that lands mid-group.  The
per-launch timeline in `search_report["pipeline"]` must account for the
run's wall, and the persistent compilation cache must produce hits in a
second cold process.
"""

import glob
import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import spark_sklearn_tpu as sst
from spark_sklearn_tpu.parallel.pipeline import (
    ChunkPipeline, LaunchItem)


def _non_time_results(gs):
    return {k: v for k, v in gs.cv_results_.items()
            if "time" not in k and k != "params"}


def _assert_exact_equal(ra, rb):
    assert set(ra) == set(rb)
    for k in ra:
        np.testing.assert_array_equal(
            np.asarray(ra[k]), np.asarray(rb[k]), err_msg=k)


def _fit(est, grid, X, y, depth, scoring=None, error_score=np.nan,
         **cfg_kw):
    cfg = sst.TpuConfig(pipeline_depth=depth, **cfg_kw)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return sst.GridSearchCV(
            est, grid, cv=2, refit=False, backend="tpu",
            scoring=scoring, error_score=error_score,
            config=cfg).fit(X, y)


class TestPipelinedParity:
    def test_logreg_sorted_multichunk_multimetric_error_score(self, digits):
        """The hardest shape: sorted chunking (8 chunks, calibration +
        fused steady state), multimetric scoring, and an invalid
        candidate masked to error_score — exact equality at any depth."""
        from sklearn.linear_model import LogisticRegression

        X, y = digits
        Xs, ys = X[:300], y[:300]
        grid = {"C": [-1.0] + np.logspace(-2, 1, 39).tolist()}
        runs = {}
        for depth in (0, 2):
            gs = _fit(LogisticRegression(max_iter=10), grid, Xs, ys,
                      depth, scoring=["accuracy", "neg_log_loss"],
                      error_score=-7.0)
            assert gs.search_report["backend"] == "tpu"
            runs[depth] = gs
        _assert_exact_equal(_non_time_results(runs[0]),
                            _non_time_results(runs[2]))
        # the invalid candidate really went through error_score masking
        assert runs[2].cv_results_["mean_test_accuracy"][0] == -7.0
        # and the pipelined run really pipelined
        assert runs[2].search_report["pipeline"]["depth"] == 2

    @pytest.mark.parametrize("fam", ["gnb", "knn"])
    def test_family_matrix_parity(self, digits, fam):
        from sklearn.naive_bayes import GaussianNB
        from sklearn.neighbors import KNeighborsClassifier

        X, y = digits
        Xs, ys = X[:240], y[:240]
        est, grid = {
            "gnb": (GaussianNB(), {"var_smoothing": [1e-9, 1e-6, 1e-3]}),
            "knn": (KNeighborsClassifier(),
                    {"n_neighbors": [3, 5], "weights":
                     ["uniform", "distance"]}),
        }[fam]
        a = _fit(est, grid, Xs, ys, 0)
        b = _fit(est, grid, Xs, ys, 3)
        assert a.search_report["backend"] == "tpu"
        _assert_exact_equal(_non_time_results(a), _non_time_results(b))

    def test_checkpoint_resume_mid_pipeline(self, digits, tmp_path):
        """Resume with surviving chunks in the MIDDLE of a compile group:
        the first live chunk (not chunk 0) must calibrate, resumed cells
        must be taken verbatim, and scores must match an uninterrupted
        run exactly."""
        from sklearn.linear_model import LogisticRegression

        X, y = digits
        Xs, ys = X[:300], y[:300]
        grid = {"C": np.logspace(-2, 1, 40).tolist()}
        full = _fit(LogisticRegression(max_iter=10), grid, Xs, ys, 0,
                    checkpoint_dir=str(tmp_path))
        ckpt_file = glob.glob(str(tmp_path / "search_*.jsonl"))[0]
        lines = open(ckpt_file).read().strip().splitlines()
        # sorted chunking: several chunks per group (5 on the 8-device
        # test mesh, 8 on one device)
        assert len(lines) >= 4
        # keep a mid-group slice only: holes before AND after
        open(ckpt_file, "w").write("\n".join(lines[2:4]) + "\n")
        resumed = _fit(LogisticRegression(max_iter=10), grid, Xs, ys, 2,
                       checkpoint_dir=str(tmp_path))
        assert resumed.search_report["n_chunks_resumed"] == 2
        _assert_exact_equal(_non_time_results(full),
                            _non_time_results(resumed))


class TestTimelineFidelity:
    def test_per_chunk_walls_cover_run_wall(self, digits):
        """The satellite contract: summing the per-launch timeline's
        stage/dispatch/compute/gather/finalize walls reconstructs >=95%
        of the measured pipeline wall (synchronous mode, where nothing
        overlaps by construction)."""
        from sklearn.linear_model import LogisticRegression

        X, y = digits
        gs = _fit(LogisticRegression(max_iter=20),
                  {"C": np.logspace(-2, 1, 40).tolist()},
                  X[:400], y[:400], 0)
        pl = gs.search_report["pipeline"]
        busy = (pl["stage_wall_s"] + pl["dispatch_wall_s"]
                + pl["compute_wall_s"] + pl["gather_wall_s"]
                + pl["finalize_wall_s"])
        assert pl["wall_s"] > 0
        assert busy >= 0.95 * pl["wall_s"], (busy, pl["wall_s"])
        # every launch is in the timeline: the first sorted chunk runs
        # fit + score + calibrate, every later chunk is one fused launch
        assert pl["n_launches"] == len(pl["launches"]) >= 5
        kinds = [t["kind"] for t in pl["launches"]]
        assert kinds[:3] == ["fit", "score", "calibrate"]
        assert set(kinds[3:]) == {"fused"}

    def test_calibration_launch_counted(self, digits):
        """The calibration's second warm score launch is real device
        work: it must appear in n_launches and score_wall_s (satellite:
        timing fidelity), and the per-task estimate must be scaled by
        the PADDED lane count."""
        from sklearn.linear_model import LogisticRegression

        X, y = digits
        gs = _fit(LogisticRegression(max_iter=10),
                  {"C": np.logspace(-2, 1, 40).tolist()},
                  X[:300], y[:300], 0)
        rep = gs.search_report
        pl = rep["pipeline"]
        n_chunks = sum(1 for t in pl["launches"]
                       if t["kind"] in ("fused", "score"))
        # one extra launch beyond the per-chunk accounting
        assert rep["n_launches"] == n_chunks + 1
        (rec,) = rep["per_group"].values()
        assert rec["score_s_per_task_calibrated"] > 0
        assert rep["score_wall_s"] > 0
        assert np.all(gs.cv_results_["mean_score_time"] > 0)

    def test_single_chunk_group_skips_calibration(self, digits):
        from sklearn.linear_model import LogisticRegression

        X, y = digits
        gs = _fit(LogisticRegression(max_iter=10), {"C": [0.5, 1.0]},
                  X[:240], y[:240], 2)
        pl = gs.search_report["pipeline"]
        kinds = [t["kind"] for t in pl["launches"]]
        assert "calibrate" not in kinds   # nothing left to calibrate for
        assert gs.search_report["n_launches"] == 1

    def test_pipelined_overlap_observable(self, digits):
        """At depth>=1 the report must expose the overlap machinery:
        precompiled program count and a nonnegative overlap fraction
        (its magnitude is hardware-dependent; its presence is not)."""
        from sklearn.linear_model import LogisticRegression

        X, y = digits
        gs = _fit(LogisticRegression(max_iter=10),
                  {"C": np.logspace(-2, 1, 40).tolist()},
                  X[:300], y[:300], 2)
        pl = gs.search_report["pipeline"]
        assert pl["depth"] == 2
        assert 0.0 <= pl["overlap_frac"] <= 1.0
        assert pl["n_precompiled"] >= 0


_CACHE_PROC = """
import json, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from sklearn.datasets import load_digits
from sklearn.linear_model import LogisticRegression
import spark_sklearn_tpu as sst
X, y = load_digits(return_X_y=True)
X = (X[:154] / 16.0).astype(np.float32); y = y[:154]
cfg = sst.TpuConfig(compilation_cache_dir=sys.argv[1],
                    persistent_cache_min_compile_s=0.0)
gs = sst.GridSearchCV(LogisticRegression(max_iter=3), {"C": [0.5, 2.0]},
                      cv=2, backend="tpu", refit=False, config=cfg)
gs.fit(X, y)
pl = dict(gs.search_report["pipeline"])
pl.pop("launches", None)
pl["jax_cache_dir"] = jax.config.jax_compilation_cache_dir
print(json.dumps(pl))
"""


class TestPersistentCache:
    def test_second_process_records_cache_hits(self, tmp_path):
        """Two cold processes sharing compilation_cache_dir: the second
        must record persistent-cache hits — the cross-process compile
        amortization the pipeline's cold path is built on."""
        outs = []
        # the TpuConfig places the cache only where the environment does
        # not (conftest sets the variable for the suite's own process)
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        for _ in range(2):
            proc = subprocess.run(
                [sys.executable, "-c", _CACHE_PROC, str(tmp_path)],
                capture_output=True, text=True, timeout=300,
                env=env)
            assert proc.returncode == 0, proc.stderr[-2000:]
            outs.append(json.loads(proc.stdout.strip().splitlines()[-1]))
        assert outs[1]["persistent_cache_hits"] > 0, outs
        # and the first process genuinely compiled (wrote the cache)
        assert outs[0]["persistent_cache_misses"] > 0, outs
        assert outs[0]["jax_cache_dir"] == str(tmp_path)

    def test_environment_places_the_cache(self, tmp_path):
        """JAX_COMPILATION_CACHE_DIR wins over a TpuConfig that names
        another directory: after the fit jax's cache points at the
        environment's path, and nothing lands in the config's."""
        env_dir, cfg_dir = tmp_path / "env", tmp_path / "cfg"
        proc = subprocess.run(
            [sys.executable, "-c", _CACHE_PROC, str(cfg_dir)],
            capture_output=True, text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "JAX_COMPILATION_CACHE_DIR": str(env_dir)})
        assert proc.returncode == 0, proc.stderr[-2000:]
        out = json.loads(proc.stdout.strip().splitlines()[-1])
        assert out["jax_cache_dir"] == str(env_dir)
        assert out["persistent_cache_misses"] > 0, out
        assert os.listdir(env_dir) and not cfg_dir.exists()

    def test_first_call_binds_the_process(self, tmp_path):
        """The cache directory is bound once per process: a later
        request for another directory changes nothing (jax's binding is
        never reset under a tenant that may be compiling), and a
        directory the user set in code is kept over the default."""
        script = (
            "import json, sys, jax\n"
            "jax.config.update('jax_platforms', 'cpu')\n"
            "import spark_sklearn_tpu as sst\n"
            "from spark_sklearn_tpu.parallel.pipeline import ("
            "enable_persistent_cache)\n"
            "a, b = sys.argv[1:3]\n"
            "if sys.argv[3] == 'preset':\n"
            "    jax.config.update('jax_compilation_cache_dir', a)\n"
            "    got = [enable_persistent_cache(sst.TpuConfig())]\n"
            "else:\n"
            "    got = [enable_persistent_cache(sst.TpuConfig("
            "compilation_cache_dir=d)) for d in (a, b)]\n"
            "got.append(jax.config.jax_compilation_cache_dir)\n"
            "print(json.dumps(got))\n")
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        a, b = str(tmp_path / "a"), str(tmp_path / "b")
        for mode, n in (("named", 3), ("preset", 2)):
            proc = subprocess.run(
                [sys.executable, "-c", script, a, b, mode],
                capture_output=True, text=True, timeout=300, env=env)
            assert proc.returncode == 0, proc.stderr[-2000:]
            got = json.loads(proc.stdout.strip().splitlines()[-1])
            assert got == [a] * n, (mode, got)

    def test_resolver_order_and_fixed_default(self, monkeypatch):
        from spark_sklearn_tpu.parallel import pipeline as pl

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        # neither named: ONE fixed path inside the checkout — no temp
        # name, pid or time in it, identical on every call
        default = pl.resolve_compile_cache_dir(sst.TpuConfig())
        assert default == os.path.join(repo, ".jax_cache")
        assert default == pl.resolve_compile_cache_dir() \
            == pl.DEFAULT_COMPILE_CACHE_DIR
        # the config names one (preferred spelling wins over the alias)
        assert pl.resolve_compile_cache_dir(
            sst.TpuConfig(compile_cache_dir="/a")) == "/a"
        assert pl.resolve_compile_cache_dir(sst.TpuConfig(
            compile_cache_dir="/a", compilation_cache_dir="/b")) == "/b"
        # the environment wins over everything
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
        assert pl.resolve_compile_cache_dir(sst.TpuConfig(
            compile_cache_dir="/a", compilation_cache_dir="/b")) == "/x"
        assert pl.resolve_compile_cache_dir() == "/x"


class TestChunkPipelineUnit:
    """Direct contract tests for the executor, no search involved."""

    def _items(self, n, order, fail_at=None):
        import jax.numpy as jnp

        def make(i):
            def stage():
                order.append(("stage", i))
                return i

            def launch(payload):
                if fail_at == i:
                    raise RuntimeError(f"boom {i}")
                order.append(("launch", i))
                return jnp.asarray(float(payload))

            def gather(out):
                return float(out)

            def finalize(host, tm):
                order.append(("finalize", i, host))

            return LaunchItem(key=f"i{i}", stage=stage, launch=launch,
                              gather=gather, finalize=finalize)

        return [make(i) for i in range(n)]

    @pytest.mark.parametrize("depth", [0, 1, 3])
    def test_finalize_order_and_results(self, depth):
        order = []
        pipe = ChunkPipeline(depth)
        pipe.run(self._items(6, order))
        pipe.close()
        fins = [e for e in order if e[0] == "finalize"]
        assert [e[1] for e in fins] == list(range(6))
        assert [e[2] for e in fins] == [float(i) for i in range(6)]
        rep = pipe.report()
        assert rep["n_launches"] == 6
        assert rep["depth"] == depth

    @pytest.mark.parametrize("depth", [0, 2])
    def test_launch_error_propagates(self, depth):
        order = []
        pipe = ChunkPipeline(depth)
        with pytest.raises(RuntimeError, match="boom 3"):
            pipe.run(self._items(6, order, fail_at=3))
        pipe.close()
        # everything before the failure still finalized
        fins = [e[1] for e in order if e[0] == "finalize"]
        assert fins == [0, 1, 2]
