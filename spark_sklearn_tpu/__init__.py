"""spark_sklearn_tpu — a TPU-native framework with the capabilities of
databricks/spark-sklearn.

Instead of fanning (parameter x fold) tasks out to Spark executors over a
broadcast dataset (reference: python/spark_sklearn/grid_search.py), this
framework lowers the task grid onto a JAX/XLA device mesh: candidates become a
``vmap`` axis, TPU chips a sharded mesh axis, and the dataset a replicated
``jax.device_put`` array over ICI, with per-candidate fits re-expressed as
jit-compiled training loops (Tier A) and a host-Python fallback preserving
full scikit-learn generality (Tier B).

Public API (mirrors the reference's __init__.py exports):
  - GridSearchCV, RandomizedSearchCV   (reference: grid_search.py)
  - Converter                          (reference: converter.py)
  - KeyedEstimator, KeyedModel         (reference: keyed_models.py)
  - gapply                             (reference: group_apply.py)
  - CSRMatrix                          (reference: udt.py CSRVectorUDT)
"""

__version__ = "0.5.0"

# The import is part of every process's set-up, so it is stamped: the
# first and last line, and the first import of each third-party root
# the package's own modules would otherwise pull in wherever they fall
# (numpy under jax; scipy, pandas and pyarrow under scikit-learn's
# ``utils.fixes``).  Nothing here is imported that the package's own
# modules do not import anyway.  ``obs/process.py`` keeps the stamps:
# ``search_report["process"]["import_by_root"]``.
import time as _time

_IMPORT_STAMPS = [("", _time.perf_counter())]


def _stamp(root):
    _IMPORT_STAMPS.append((root, _time.perf_counter()))


import numpy  # noqa: E402,F401
_stamp("numpy")
import jax  # noqa: E402,F401
import jax.numpy  # noqa: E402,F401
_stamp("jax")
import jax.experimental.pallas  # noqa: E402,F401 — ops/tree_hist.py's
import jax.experimental.pallas.tpu  # noqa: E402,F401
_stamp("jax.experimental.pallas")
import scipy.sparse  # noqa: E402,F401
import scipy.special  # noqa: E402,F401
import scipy.stats  # noqa: E402,F401
_stamp("scipy")
import pandas  # noqa: E402,F401 — keyed/'s, and sklearn.utils.fixes'
_stamp("pandas")
import sklearn.base  # noqa: E402,F401
import sklearn.callback  # noqa: E402,F401
import sklearn.model_selection  # noqa: E402,F401
_stamp("sklearn")

import spark_sklearn_tpu.models  # noqa: F401,E402 — registers Tier-A families
from spark_sklearn_tpu.models.base import NotCompiledError
from spark_sklearn_tpu.search.grid import GridSearchCV, RandomizedSearchCV
from spark_sklearn_tpu.search.halving import (
    HalvingGridSearchCV,
    HalvingRandomSearchCV,
)
from spark_sklearn_tpu.parallel.mesh import TpuConfig, build_mesh
from spark_sklearn_tpu.convert.converter import Converter
from spark_sklearn_tpu.keyed.keyed import KeyedEstimator, KeyedModel
from spark_sklearn_tpu.keyed.gapply import compiled_group_func, gapply
from spark_sklearn_tpu.sparse.csr import CSRMatrix
from spark_sklearn_tpu.utils.session import (
    TpuSession,
    createLocalSparkSession,
    createLocalTpuSession,
    init_distributed,
)
from spark_sklearn_tpu.serve import (
    AdmissionError,
    SearchCancelledError,
    SearchExecutor,
    SearchFuture,
)

__all__ = [
    "GridSearchCV",
    "RandomizedSearchCV",
    "HalvingGridSearchCV",
    "HalvingRandomSearchCV",
    "AdmissionError",
    "NotCompiledError",
    "SearchCancelledError",
    "SearchExecutor",
    "SearchFuture",
    "Converter",
    "KeyedEstimator",
    "KeyedModel",
    "gapply",
    "compiled_group_func",
    "CSRMatrix",
    "TpuConfig",
    "TpuSession",
    "build_mesh",
    "createLocalTpuSession",
    "createLocalSparkSession",
    "init_distributed",
    "__version__",
]

from spark_sklearn_tpu.obs import process as _process  # noqa: E402

_stamp("")
_process.note_import(_IMPORT_STAMPS)
