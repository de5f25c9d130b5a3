"""Device-resident data plane — the session-scoped broadcast cache.

The reference amortized dataset shipping with ``sc.broadcast``: X/y went
to every executor ONCE and every task reused the handle (reference:
grid_search.py ``X_bc = sc.broadcast(X)``).  Before this module the TPU
rebuild re-shipped per search: every ``fit`` re-``device_put`` X/y and
every fold mask even inside one :class:`~spark_sklearn_tpu.utils.
session.TpuSession`, and task-batched families re-tiled the fold masks
on the HOST (``np.tile`` to ``(width x n_folds, n_samples)``) once per
compile group — a multi-MB host allocation plus transfer per group, and
per RELAUNCH in OOM recovery.  Ousterhout-style overhead analysis of
distributed ML (arXiv:1612.01437) and DrJAX's device-resident MapReduce
primitives (arXiv:2403.07128) both land on the same answer: keep
operands resident, size the fan-out to the measured cost, never re-ship
per task.

:class:`DataPlane` is that answer here:

  - **fingerprint-keyed**: entries key on a content digest (blake2b of
    bytes + shape + dtype) so two searches over the same data share one
    upload no matter how the arrays were constructed;
  - **sharding-aware**: the key includes the target sharding (mesh
    device order + partition spec), so a replicated X and a
    data-sharded X are distinct residents and a mesh change can never
    serve a stale layout;
  - **byte-budgeted LRU**: entries are evicted least-recently-used once
    the budget (``TpuConfig.dataplane_bytes``) is exceeded — a
    long-lived session cycling many datasets bounds its own HBM;
  - **on-device mask tiling**: :meth:`DataPlane.tiled` replaces the
    host ``np.tile`` + upload with a one-time base-mask upload plus a
    tiny compiled broadcast per (width, sharding) whose result is
    itself cached — fold masks transfer host->device at most once per
    search, not once per group/launch;
  - **observable**: hits/misses/bytes land in ``search_report
    ["dataplane"]`` (schema pinned in ``obs.metrics``), every real
    transfer records a ``dataplane.upload`` span carrying its byte
    count (``tools/trace_summary.py`` digests them into a "bytes
    host->device" line).

Cache entries fingerprint content AT UPLOAD TIME: mutating an array in
place after a search produces a new fingerprint (and a fresh upload) on
the next search — entries are never revalidated on hit.

Plane entries must never be donated to XLA (donation invalidates the
buffer for every later consumer); per-chunk dynamic-parameter staging
bypasses the cache via :func:`upload`.
"""

from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict
from typing import Any, Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from spark_sklearn_tpu.obs import telemetry as _telemetry
from spark_sklearn_tpu.obs.trace import get_tracer
from spark_sklearn_tpu.utils import keycheck as _keycheck
from spark_sklearn_tpu.utils.locks import named_lock, named_rlock

__all__ = [
    "DataPlane",
    "bytes_uploaded",
    "fingerprint",
    "get_dataplane",
    "plane_for",
    "upload",
]

#: default byte budget (256 MiB) — enough to keep a bench-scale dataset,
#: its fold masks and a few tiled-mask widths resident, small enough to
#: be harmless on the CPU test mesh.
DEFAULT_BYTE_BUDGET = 256 * 2 ** 20

#: process-wide host->device transfer accounting (every ``upload`` call,
#: cacheable or not) — the pipeline's per-launch ``stage_bytes`` and the
#: trace digest read this.
_TOTALS = {"bytes": 0, "uploads": 0}
_TOTALS_LOCK = named_lock("dataplane._TOTALS_LOCK")


def bytes_uploaded() -> int:
    """Cumulative host->device bytes this process transferred through
    the data plane (cache-miss broadcasts AND per-chunk staging).
    Callers snapshot before/after a phase and report the delta."""
    with _TOTALS_LOCK:
        return _TOTALS["bytes"]


def upload(arr: np.ndarray, sharding=None, label: str = "staging"):
    """``jax.device_put`` with byte accounting and a traced
    ``dataplane.upload`` span (the span carries ``bytes`` so transfer
    regressions show up in the trace digest).  This is the ONLY
    device_put the search engine's data paths use — cached entries go
    through :meth:`DataPlane.put`, which calls this on a miss."""
    nbytes = int(getattr(arr, "nbytes", 0))
    with get_tracer().span("dataplane.upload", bytes=nbytes, label=label):
        out = (jax.device_put(arr, sharding) if sharding is not None
               else jax.device_put(arr))
    with _TOTALS_LOCK:
        _TOTALS["bytes"] += nbytes
        _TOTALS["uploads"] += 1
    # fleet telemetry (outside the totals lock; exact no-op off)
    _telemetry.note_h2d(nbytes)
    return out


def _csr_parts(arr):
    """``(data, indices, indptr, shape)`` of a CSR-like host matrix
    (scipy csr/csc or :class:`~spark_sklearn_tpu.sparse.csr.CSRMatrix`),
    or None for anything else.  Duck-typed so the data plane never
    imports scipy just to recognise its matrices."""
    if isinstance(arr, np.ndarray) or not hasattr(arr, "indptr"):
        return None
    data = getattr(arr, "data", None)
    indices = getattr(arr, "indices", None)
    if data is None or indices is None:
        return None
    return (np.asarray(data), np.asarray(indices),
            np.asarray(arr.indptr), tuple(int(s) for s in arr.shape))


def fingerprint(arr: np.ndarray) -> str:
    """Content digest of a host array: blake2b over the raw bytes plus
    shape/dtype.  Full-content (not sampled) — a wrong cache hit would
    silently corrupt scores, and hashing runs at ~1 GB/s, far cheaper
    than the transfer it saves.

    CSR-like inputs digest their ``(data, indices, indptr, shape)``
    components directly — fingerprinting a wide sparse X must never
    allocate its dense form (pinned by test_dataplane.py)."""
    parts = _csr_parts(arr)
    h = hashlib.blake2b(digest_size=16)
    with get_tracer().span("dataplane.fingerprint",
                           bytes=int(getattr(arr, "nbytes", 0))):
        if parts is not None:
            data, indices, indptr, shape = parts
            h.update(repr(("csr", shape, data.dtype.str,
                           indices.dtype.str)).encode())
            for a in (data, indices, indptr):
                a = np.ascontiguousarray(a)
                h.update(a.data if a.flags["C_CONTIGUOUS"]
                         else a.tobytes())
            return h.hexdigest()
        a = np.ascontiguousarray(arr)
        h.update(repr((a.shape, a.dtype.str)).encode())
        h.update(a.data if a.flags["C_CONTIGUOUS"] else a.tobytes())
        return h.hexdigest()


def _sharding_key(sharding) -> Any:
    """Hashable identity of a placement: device order + partition spec
    (+ memory kind).  Two meshes over the same chips in a different
    order are different placements."""
    if sharding is None:
        return None
    mesh = getattr(sharding, "mesh", None)
    if mesh is not None:
        devs = tuple(d.id for d in np.asarray(mesh.devices).flat)
        shape = tuple(sorted(dict(mesh.shape).items()))
    else:
        devs = tuple(sorted(d.id for d in sharding.device_set))
        shape = None
    return (type(sharding).__name__, devs, shape,
            repr(getattr(sharding, "spec", None)),
            getattr(sharding, "memory_kind", None))


class DataPlane:
    """Fingerprint-keyed, byte-budgeted LRU cache of device arrays.

    One process-global instance (:func:`get_dataplane`) is shared by
    every search; a :class:`~spark_sklearn_tpu.utils.session.TpuSession`
    sizes its budget at construction (``TpuConfig.dataplane_bytes``).
    Thread-safe: the pipeline's stage thread and the fault supervisor's
    recovery threads may all reach it concurrently.
    """

    def __init__(self, byte_budget: int = DEFAULT_BYTE_BUDGET):
        self._lock = named_rlock("dataplane.DataPlane._lock")
        #: key -> (device array, nbytes, tenant, label)
        self._entries: "OrderedDict[Any, Tuple[Any, int, Any, str]]" = \
            OrderedDict()
        self._bytes = 0
        self.byte_budget = int(byte_budget)
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.bytes_uploaded = 0       # miss uploads through put()/zeros()
        self.bytes_tiled = 0          # device-side tile materializations
        self.bytes_derived = 0        # device-computed derived buffers
        #: compiled tile programs keyed by (shape, dtype, reps, sharding)
        self._tile_programs: Dict[Any, Any] = {}
        #: multi-tenant accounting (serve/executor.py): per-tenant byte
        #: quotas and current charged usage.  Entries uploaded with a
        #: tenant are charged to it; a tenant over quota evicts its OWN
        #: LRU entries, and the global budget pass prefers victims that
        #: are unowned, the inserter's own, or over-quota — so one
        #: tenant's pressure cannot evict another's resident X/y while
        #: that tenant stays within its quota.
        self._tenant_quotas: Dict[Any, int] = {}
        self._tenant_bytes: Dict[Any, int] = {}

    # -- sizing ----------------------------------------------------------
    def configure(self, byte_budget: Optional[int]) -> "DataPlane":
        """Set the byte budget (evicting LRU entries if it shrank);
        ``None`` keeps the current budget."""
        if byte_budget is None:
            return self
        with self._lock:
            self.byte_budget = int(byte_budget)
            self._evict_over_budget()
        return self

    def _uncharge(self, tenant, nbytes: int) -> None:
        """Drop ``nbytes`` from a tenant's charged usage; usage
        reaching zero removes the accounting row.  (Callers hold the
        reentrant plane lock; taken again for standalone safety.)"""
        if tenant is None:
            return
        with self._lock:
            left = self._tenant_bytes.get(tenant, 0) - int(nbytes)
            if left > 0:
                self._tenant_bytes[tenant] = left
            else:
                self._tenant_bytes.pop(tenant, None)

    def _pop_entry(self, key) -> None:
        with self._lock:
            _, nbytes, tenant, _ = self._entries.pop(key)
            self._bytes -= nbytes
            self._uncharge(tenant, nbytes)
            self.evictions += 1

    def _over_quota(self, tenant) -> bool:
        quota = self._tenant_quotas.get(tenant)
        return bool(quota) and self._tenant_bytes.get(tenant, 0) > quota

    def _evict_over_budget(self, keep: Any = None,
                           inserting: Any = None) -> None:
        # every caller already holds the (reentrant) plane lock; taking
        # it again makes the helper safe on its own rather than by
        # call-site convention
        with self._lock:
            while self._bytes > self.byte_budget and len(self._entries) > 1:
                # tenant isolation: prefer victims that are unowned,
                # the inserter's own, or belong to an over-quota
                # tenant; a tenant within its quota is only evicted by
                # global pressure when no such victim exists (e.g. the
                # quotas were configured to exceed the plane budget)
                key = None
                for k, (_, _, t, _lb) in self._entries.items():
                    if k == keep:
                        continue
                    if t is None or t == inserting or self._over_quota(t):
                        key = k
                        break
                if key is None:
                    key = next(iter(self._entries))
                if key == keep:
                    # never evict the entry being returned; rotate it to
                    # the MRU end and take the next-oldest instead
                    self._entries.move_to_end(key)
                    key = next(iter(self._entries))
                    if key == keep:
                        break
                self._pop_entry(key)
        # a single oversized entry may exceed the budget on its own; it
        # stays (dropping it would force a re-upload every search) and
        # becomes the next LRU victim

    # -- residency -------------------------------------------------------
    def _get(self, key):
        with self._lock:
            hit = self._entries.get(key)
            if hit is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                return hit[0]
            return None

    def _insert(self, key, value, nbytes: int, tenant: Any = None,
                label: str = ""):
        with self._lock:
            if key in self._entries:
                return
            # per-tenant quota: a tenant exceeding its own quota evicts
            # its OWN least-recently-used residents first — other
            # tenants' entries are untouchable here by construction
            quota = self._tenant_quotas.get(tenant)
            if tenant is not None and quota:
                while self._tenant_bytes.get(tenant, 0) + int(nbytes) \
                        > quota:
                    victim = next(
                        (k for k, (_, _, t, _lb) in self._entries.items()
                         if t == tenant), None)
                    if victim is None:
                        break
                    self._pop_entry(victim)
            self._entries[key] = (value, int(nbytes), tenant, label)
            self._bytes += int(nbytes)
            if tenant is not None:
                self._tenant_bytes[tenant] = \
                    self._tenant_bytes.get(tenant, 0) + int(nbytes)
            self._evict_over_budget(keep=key, inserting=tenant)

    # -- multi-tenant quotas ---------------------------------------------
    def set_tenant_quota(self, tenant, nbytes: int) -> None:
        """Register (or update) a tenant's resident byte quota.  New
        inserts charged to the tenant evict its own LRU entries beyond
        it; 0/None removes the quota (usage accounting remains)."""
        with self._lock:
            if nbytes:
                self._tenant_quotas[tenant] = int(nbytes)
            else:
                self._tenant_quotas.pop(tenant, None)

    def tenant_usage(self, tenant) -> int:
        """Bytes currently resident and charged to ``tenant``."""
        with self._lock:
            return self._tenant_bytes.get(tenant, 0)

    def tenant_usage_all(self) -> Dict[Any, int]:
        """Resident bytes charged per tenant (the fleet endpoint's
        per-tenant residency gauge)."""
        with self._lock:
            return dict(self._tenant_bytes)

    def release_tenant(self, tenant) -> int:
        """Release a tenant's plane charge (a cancelled or finished
        tenant's last search): its entries become unowned — first in
        line for LRU eviction, but still servable as hits while they
        survive — its usage resets to zero and its quota is dropped.
        Returns the byte count released."""
        with self._lock:
            released = 0
            for k in list(self._entries):
                value, nbytes, t, label = self._entries[k]
                if t == tenant:
                    self._entries[k] = (value, nbytes, None, label)
                    self._entries.move_to_end(k, last=False)
                    released += nbytes
            self._tenant_bytes.pop(tenant, None)
            self._tenant_quotas.pop(tenant, None)
            return released

    def demote(self, label_prefix: str, tenant) -> int:
        """Un-charge a tenant's entries whose label starts with
        ``label_prefix``: they become unowned, stop counting against
        the tenant's quota, and rotate to the LRU front — still
        servable as hits while they survive, but first in line for
        eviction.  The successive-halving rung barrier
        (search/halving.py) calls this with the previous rung's
        namespace (``"mask.r0."``) so a tenant's data-plane charge
        shrinks as rungs retire candidates: that rung's subsampled
        fold masks and wide tiled masks are exactly the buffers the
        surviving (narrower) rungs no longer need — and the scoped
        prefix can never touch a sibling search's live masks under
        the same tenant.  Returns the byte count demoted."""
        with self._lock:
            released = 0
            for k in list(self._entries):
                value, nbytes, t, label = self._entries[k]
                if t == tenant and label.startswith(label_prefix):
                    self._entries[k] = (value, nbytes, None, label)
                    self._entries.move_to_end(k, last=False)
                    self._uncharge(tenant, nbytes)
                    released += nbytes
            return released

    def put(self, arr: np.ndarray, sharding, label: str = "array",
            tenant: Any = None):
        """The cached ``device_put``: returns the resident device array
        for this (content, sharding), uploading at most once while the
        entry survives the budget.

        The whole miss path runs under the plane lock: two threads
        racing on the same key (stage thread vs a supervisor recovery
        relaunch) must not both upload — transfers serialize on the
        host->device stream anyway, and a double upload would inflate
        the ``bytes_uploaded`` counter the warm-search acceptance
        asserts to be zero."""
        key = ("host", fingerprint(arr), _sharding_key(sharding))
        with self._lock:
            cached = self._get(key)
            if cached is not None:
                return cached
            self.misses += 1
            self.bytes_uploaded += int(arr.nbytes)
            dev = upload(arr, sharding, label=label)
            self._insert(key, dev, arr.nbytes, tenant=tenant,
                         label=label)
            return dev

    def zeros(self, n: int, dtype, sharding, tenant: Any = None):
        """Cached all-zero launch operand (the all-static group's
        ``_pad`` axis definition) — uploaded once per (n, dtype,
        sharding), never per launch."""
        host = np.zeros(int(n), dtype=dtype)
        return self.put(host, sharding, label="zeros", tenant=tenant)

    def tiled(self, base: np.ndarray, base_dev, reps: int, out_sharding,
              label: str = "mask.tiled", fp: Optional[str] = None,
              tenant: Any = None):
        """Device-tiled ``(reps * rows, cols)`` view of ``base`` — the
        on-device replacement for host ``np.tile`` + upload.

        ``base_dev`` is the already-resident base (e.g. the fold masks'
        replicated upload); the tile itself is a tiny compiled
        broadcast whose RESULT is cached per (content, reps, sharding),
        so a width revisited by any later group, OOM relaunch or search
        costs one cache lookup and zero transfer.  Pass ``fp`` (a
        :func:`fingerprint` of ``base``) to skip re-hashing an array
        the caller already fingerprinted — hot-path callers memoize it
        once per search."""
        fp = fp or fingerprint(base)
        key = ("tile", fp, int(reps), _sharding_key(out_sharding))
        with self._lock:
            cached = self._get(key)
            if cached is not None:
                return cached
            self.misses += 1
            prog_key = (base.shape, str(base.dtype), int(reps),
                        _sharding_key(out_sharding))
            tile_fn = self._tile_programs.get(prog_key)
            if tile_fn is None:
                tile_fn = jax.jit(
                    lambda m, _r=int(reps): jnp.tile(m, (_r, 1)),
                    out_shardings=out_sharding)
                self._tile_programs[prog_key] = tile_fn
            nbytes = int(base.nbytes) * int(reps)
            with get_tracer().span("dataplane.tile", bytes=nbytes,
                                   reps=int(reps), label=label):
                dev = tile_fn(base_dev)
            self.bytes_tiled += nbytes
            self._insert(key, dev, nbytes, tenant=tenant, label=label)
            return dev

    def derived(self, key_parts: Tuple, maker, nbytes: int,
                label: str = "derived", tenant: Any = None):
        """Cached DEVICE-COMPUTED buffer — the resident home of arrays
        that never cross host->device (e.g. the shared-prefix
        scheduler's per-fold transformed design matrices).  Returns
        ``(device_array, hit)``; ``maker()`` runs at most once while
        the entry survives the budget and its result is charged
        ``nbytes`` against the tenant's quota like any upload.

        ``key_parts`` IS the provenance: callers key on the content
        digests of every input the computation consumed (prefix-config
        digest, source-X fingerprint, fold-mask fingerprint, sharding)
        so a mutated source yields a fresh key — invalidation by
        construction, same contract as :meth:`put` (entries are never
        revalidated on hit).  The whole miss path runs under the plane
        lock so two searches racing on one digest compute it once."""
        key = ("derived",) + tuple(key_parts)
        # equal keys must mean equal bytes: one key observed with two
        # different nbytes is content drift the digests failed to
        # capture — surfaced as a key collision under SST_KEYCHECK=1
        _keycheck.note("dataplane", key,
                       fields={"nbytes": int(nbytes)}, detail=label)
        with self._lock:
            cached = self._get(key)
            if cached is not None:
                return cached, True
            self.misses += 1
            nbytes = int(nbytes)
            with get_tracer().span("dataplane.derive", bytes=nbytes,
                                   label=label):
                dev = maker()
            self.bytes_derived += nbytes
            self._insert(key, dev, nbytes, tenant=tenant, label=label)
            return dev, False

    # -- introspection ---------------------------------------------------
    @property
    def n_entries(self) -> int:
        with self._lock:
            return len(self._entries)

    @property
    def bytes_in_cache(self) -> int:
        with self._lock:
            return self._bytes

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "hits": self.hits, "misses": self.misses,
                "evictions": self.evictions,
                "bytes_uploaded": self.bytes_uploaded,
                "bytes_tiled": self.bytes_tiled,
                "n_entries": len(self._entries),
                "bytes_in_cache": self._bytes,
                "budget_bytes": self.byte_budget,
            }

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._bytes = 0
            self._tile_programs.clear()
            self._tenant_bytes.clear()
            self._tenant_quotas.clear()


_PLANE: Optional[DataPlane] = None
_PLANE_LOCK = named_lock("dataplane._PLANE_LOCK")


def get_dataplane() -> DataPlane:
    """The process-global plane (created on first use)."""
    global _PLANE
    with _PLANE_LOCK:
        if _PLANE is None:
            _PLANE = DataPlane()
        return _PLANE


def plane_for(config) -> Optional[DataPlane]:
    """The plane a search should use under ``config``, budget applied —
    or ``None`` when ``TpuConfig(dataplane_bytes=0)`` disabled it (the
    legacy per-search ``device_put`` escape hatch)."""
    budget = getattr(config, "dataplane_bytes", DEFAULT_BYTE_BUDGET)
    if not budget or budget <= 0:
        return None
    return get_dataplane().configure(int(budget))


def snapshot_counters(plane: Optional[DataPlane]) -> Dict[str, int]:
    """Counter snapshot for per-search deltas (``search_report
    ["dataplane"]``)."""
    snap = {"total_bytes": bytes_uploaded()}
    if plane is not None:
        s = plane.stats()
        snap.update({k: s[k] for k in (
            "hits", "misses", "evictions", "bytes_uploaded",
            "bytes_tiled")})
    return snap


def report_block(plane: Optional[DataPlane], before: Dict[str, int],
                 mask_tiling: str = "n/a") -> Dict[str, Any]:
    """The rendered ``search_report["dataplane"]`` block (schema pinned
    in ``obs.metrics.DATAPLANE_BLOCK_SCHEMA``): this search's cache
    traffic plus the plane's end-of-search state."""
    total_delta = bytes_uploaded() - before.get("total_bytes", 0)
    if plane is None:
        return {"enabled": False, "hits": 0, "misses": 0, "evictions": 0,
                "bytes_uploaded": 0, "bytes_tiled": 0,
                "bytes_staged": total_delta, "n_entries": 0,
                "bytes_in_cache": 0, "budget_bytes": 0,
                "mask_tiling": mask_tiling}
    s = plane.stats()
    cacheable = s["bytes_uploaded"] - before.get("bytes_uploaded", 0)
    return {
        "enabled": True,
        "hits": s["hits"] - before.get("hits", 0),
        "misses": s["misses"] - before.get("misses", 0),
        "evictions": s["evictions"] - before.get("evictions", 0),
        "bytes_uploaded": cacheable,
        "bytes_tiled": s["bytes_tiled"] - before.get("bytes_tiled", 0),
        "bytes_staged": max(0, total_delta - cacheable),
        "n_entries": s["n_entries"],
        "bytes_in_cache": s["bytes_in_cache"],
        "budget_bytes": s["budget_bytes"],
        "mask_tiling": mask_tiling,
    }
