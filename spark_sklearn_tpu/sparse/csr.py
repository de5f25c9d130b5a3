"""Sparse row-matrix container — the UDT replacement.

The reference's CSRVectorUDT (reference: python/spark_sklearn/udt.py) teaches
Spark DataFrames to carry scipy `csr_matrix` rows so sparse features reach
sklearn without densifying.  There is no Spark SQL engine here; the
equivalent capability is a typed container that moves CSR data between
scipy, numpy (pandas cells), and JAX:

  - `CSRMatrix.from_scipy` / `.to_scipy` — lossless scipy round trip
  - `.to_dense()` — jnp dense array (the TPU compute format; XLA has no
    first-class CSR, and for MXU-sized problems dense is the fast path)
  - `.to_bcoo()` — `jax.experimental.sparse.BCOO` for genuinely sparse
    compute (canonical: duplicate-free, row-major sorted indices)
  - `.serialize()` / `CSRMatrix.deserialize` — the UDT contract (sqlType/
    serialize/deserialize) as a plain tuple-of-arrays schema

`SparseOperand` is the host-side staging form of a BCOO operand: the
search engine uploads its `values`/`indices` components separately (each
nnz-proportional) and reassembles the device BCOO, so upload accounting,
dataplane fingerprints and the ledger all price nnz — never n x d.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

#: first index value that no longer fits an int32 — matrices at or past
#: this size (any dimension, or nnz) carry int64 indices end to end
_INT32_MAX = np.iinfo(np.int32).max


def index_dtype(*extents) -> np.dtype:
    """int32 when every extent (dims, nnz) fits, int64 past 2**31-1 —
    silent int32 truncation on a huge-axis matrix would alias rows."""
    if any(int(e) > _INT32_MAX for e in extents):
        return np.dtype(np.int64)
    return np.dtype(np.int32)


class CSRMatrix:
    """Compressed sparse row matrix: (data, indices, indptr, shape)."""

    def __init__(self, data, indices, indptr, shape: Tuple[int, int]):
        self.data = np.asarray(data)
        shape = (int(shape[0]), int(shape[1]))
        # indices index columns (< shape[1]); indptr indexes into data
        # (<= nnz) — size each independently so a tiny-nnz matrix over a
        # huge axis keeps exactly the dtypes it needs
        self.indices = np.asarray(
            indices, dtype=index_dtype(shape[1], 0))
        self.indptr = np.asarray(
            indptr, dtype=index_dtype(len(self.data)))
        self.shape = shape

    # -- scipy bridge ----------------------------------------------------
    @classmethod
    def from_scipy(cls, m) -> "CSRMatrix":
        m = m.tocsr()
        return cls(m.data, m.indices, m.indptr, m.shape)

    def to_scipy(self):
        from scipy.sparse import csr_matrix
        return csr_matrix((self.data, self.indices, self.indptr),
                          shape=self.shape)

    # -- device bridges --------------------------------------------------
    def to_dense(self, dtype=np.float32):
        import jax.numpy as jnp
        if dtype == np.float32 and \
                self.indices.dtype == np.int32 and \
                self.indptr.dtype == np.int32:
            from spark_sklearn_tpu.utils.native import csr_to_dense
            return jnp.asarray(csr_to_dense(
                self.data, self.indices, self.indptr, self.shape))
        return jnp.asarray(self.to_scipy().toarray().astype(dtype))

    def to_bcoo(self, dtype=np.float32):
        from jax.experimental import sparse as jsparse
        op = SparseOperand.from_csr(self, dtype=dtype)
        return jsparse.BCOO(
            (op.values, op.indices), shape=op.shape,
            indices_sorted=True, unique_indices=True)

    # -- UDT-style serialization (reference: udt.py sqlType/serialize) ---
    def serialize(self):
        return (self.data, self.indices, self.indptr,
                np.asarray(self.shape, dtype=np.int64))

    @classmethod
    def deserialize(cls, datum) -> "CSRMatrix":
        data, indices, indptr, shape = datum
        return cls(data, indices, indptr, tuple(int(s) for s in shape))

    # -- conveniences ----------------------------------------------------
    @property
    def nnz(self) -> int:
        return int(len(self.data))

    @property
    def nbytes(self) -> int:
        """Component bytes (data + indices + indptr) — what footprint
        pricing and upload accounting should see, never n x d."""
        return int(self.data.nbytes + self.indices.nbytes
                   + self.indptr.nbytes)

    def __repr__(self):
        return (f"CSRMatrix(shape={self.shape}, nnz={self.nnz}, "
                f"dtype={self.data.dtype})")

    def __eq__(self, other):
        if not isinstance(other, CSRMatrix):
            return NotImplemented
        return (self.shape == other.shape
                and np.array_equal(self.data, other.data)
                and np.array_equal(self.indices, other.indices)
                and np.array_equal(self.indptr, other.indptr))


class SparseOperand:
    """Host-side staged form of one BCOO device operand.

    Carries the canonical COO components (`values` (nnz,), `indices`
    (nnz, 2)) the engine uploads separately — each transfer is
    nnz-proportional and individually fingerprinted by the data plane —
    plus the facts (`shape`, `nnz`) that enter program-store keys and
    checkpoint fingerprints as the sparse signature."""

    __slots__ = ("values", "indices", "shape")

    def __init__(self, values, indices, shape):
        self.values = np.ascontiguousarray(values)
        self.indices = np.ascontiguousarray(indices)
        self.shape = (int(shape[0]), int(shape[1]))

    @classmethod
    def from_csr(cls, m, dtype=np.float32) -> "SparseOperand":
        """Canonical (duplicate-free, row-major sorted) COO components
        from any CSR-like matrix (scipy sparse or CSRMatrix)."""
        if isinstance(m, CSRMatrix):
            m = m.to_scipy()
        m = m.tocsr().copy()
        # scipy canonical form: sums duplicates AND sorts each row's
        # column indices, so the row-major COO walk below emits sorted,
        # unique coordinates — the flags to_bcoo() then asserts
        m.sum_duplicates()
        coo = m.tocoo()
        idt = index_dtype(m.shape[0], m.shape[1], m.nnz)
        idx = np.stack([coo.row.astype(idt), coo.col.astype(idt)],
                       axis=1)
        return cls(coo.data.astype(dtype, copy=False), idx, m.shape)

    @property
    def nnz(self) -> int:
        return int(self.values.shape[0])

    @property
    def nbytes(self) -> int:
        return int(self.values.nbytes + self.indices.nbytes)

    def signature(self) -> tuple:
        """The sparse program signature: enough to distinguish two
        compiled programs whose dense shapes agree but whose sparse
        layouts differ (joins ProgramStore keys and checkpoint
        fingerprints)."""
        return ("bcoo", self.shape, self.nnz,
                str(self.values.dtype), str(self.indices.dtype))

    def to_bcoo(self, values=None, indices=None):
        """Assemble the device BCOO from already-uploaded components
        (or the host ones, for tests)."""
        from jax.experimental import sparse as jsparse
        return jsparse.BCOO(
            (self.values if values is None else values,
             self.indices if indices is None else indices),
            shape=self.shape, indices_sorted=True, unique_indices=True)


_BCOO_EXPORT_REGISTERED = False


def register_bcoo_export() -> bool:
    """Teach ``jax.export`` to serialize BCOO-carrying pytrees so the
    ProgramStore can persist sparse Tier-A programs (AOT prewarm).
    Idempotent (a name another engine in this process already
    registered counts as success); returns True."""
    global _BCOO_EXPORT_REGISTERED
    if _BCOO_EXPORT_REGISTERED:
        return True
    import json

    from jax import export as jexport
    from jax.experimental import sparse as jsparse

    def _ser(aux):
        d = dict(aux)
        d["shape"] = [int(s) for s in d["shape"]]
        return json.dumps(d, sort_keys=True).encode()

    def _de(b):
        d = json.loads(b.decode())
        d["shape"] = tuple(d["shape"])
        return d

    try:
        jexport.register_pytree_node_serialization(
            jsparse.BCOO,
            serialized_name="jax.experimental.sparse.BCOO",
            serialize_auxdata=_ser,
            deserialize_auxdata=_de)
    except ValueError:
        # already registered (e.g. a second engine in-process): that is
        # success for our purposes
        pass
    _BCOO_EXPORT_REGISTERED = True
    return True
