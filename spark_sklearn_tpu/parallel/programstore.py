"""Persistent AOT program & plan store — zero-cold-start sessions.

A cold process pays the whole compile wall again: the persistent XLA
compilation cache (parallel/pipeline.py) amortizes the HLO->binary step
across processes, but every fresh worker still re-walks python -> jaxpr
-> StableHLO for every compile group before it can even ASK that cache.
At fleet scale (ROADMAP item 1: many workers serving many users'
searches) that wall is paid per worker, not per program — the same
cost spark-sklearn's shared cluster amortized by keeping one JVM warm,
and the cost DrJAX-style reusable compiled programs remove by making
the compiled artifact itself the shared object.

:class:`ProgramStore` is the on-disk artifact tier under the in-process
program cache (search/grid.py ``_PROGRAM_CACHE``):

  - **artifacts** are ``jax.export``-serialized programs (portable
    StableHLO + calling convention), keyed by (program kind, estimator
    family, compile-group structure digest, launch-geometry width — all
    folded into a content digest — and the abstract input signature),
    stored under a directory versioned by store format and an
    environment fingerprint (jax/jaxlib/package versions, platform,
    device fleet).  ``Compiled.serialize`` — a backend-specific XLA
    executable — is not exposed by this jax version on any backend here;
    the StableHLO artifact skips the expensive python->jaxpr->HLO walk
    and leaves the final HLO->binary step to the persistent XLA cache,
    which both the publishing and the loading process hit with the SAME
    module because both execute the stored bytes (see
    :class:`StoredProgram`).
  - **hardened like the checkpoint journal**: atomic writes (tmp +
    fsync + ``os.replace``), version/topology mismatch -> clean miss
    and JIT fallback, corrupt artifact -> quarantine + recompile —
    never a failed search.
  - **byte-budgeted**: oldest artifacts are evicted once the store
    exceeds ``TpuConfig.program_store_bytes``.
  - **plans ride along**: the launch-geometry plan cache and the
    :class:`~spark_sklearn_tpu.parallel.taskgrid.GeometryCostModel`
    EMA state persist next to the programs (``plans.json``), so a fresh
    process plans the SAME chunk widths — and therefore requests the
    same stored programs — without re-measuring.
  - **prewarmable**: a manifest written by a finished search's session
    (:meth:`~spark_sklearn_tpu.utils.session.TpuSession.
    write_prewarm_manifest`) names the artifacts it used;
    ``TpuSession(config=TpuConfig(prewarm_manifest=...))`` loads them
    at init so the first chunk of the first search resolves from
    memory.
  - **observable**: ``search_report["programstore"]`` (schema pinned in
    ``obs.metrics.PROGRAMSTORE_BLOCK_SCHEMA``) and ``programstore.load``
    / ``programstore.save`` spans carrying byte counts and hit flags
    (``tools/trace_summary.py`` digests them into a compile line).

Execution contract: a process that PUBLISHES an artifact also executes
the published bytes (serialize -> write -> deserialize -> run), so the
loading process compiles the byte-identical module and the persistent
XLA cache covers the binary too.  Results are bit-identical to the jit
path — the artifact is the same jaxpr's StableHLO, and every failure
mode (unsupported export, version drift, corruption) falls back to
plain jit with the same program.
"""

from __future__ import annotations

import hashlib
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np
import jax

from spark_sklearn_tpu.obs import telemetry as _telemetry
from spark_sklearn_tpu.obs.log import get_logger
from spark_sklearn_tpu.obs import process as _process
from spark_sklearn_tpu.obs.trace import get_tracer
# crash-safe publish (tmp + fsync + os.replace): the one hardened
# write path every store file (artifacts, plans.json, manifests) goes
# through — shared with the flight recorder via utils/atomic.py
from spark_sklearn_tpu.utils import keycheck as _keycheck
from spark_sklearn_tpu.utils.atomic import atomic_write as _atomic_write
from spark_sklearn_tpu.utils.locks import named_lock

logger = get_logger(__name__)

__all__ = [
    "DEFAULT_STORE_BUDGET",
    "STORE_FORMAT",
    "ProgramStore",
    "StoredProgram",
    "activate_store",
    "active_store",
    "deactivate_store",
    "maybe_wrap",
    "report_block",
    "snapshot_counters",
]

#: on-disk format version: bump when the artifact layout changes (2:
#: the launch programs' output tree is a LaunchResult) — old stores
#: become clean misses, never parse errors.
STORE_FORMAT = 2

#: artifact file magic (format version baked in).
_MAGIC = b"SSTPROG2"

#: default store byte budget (512 MiB): a few hundred bench-scale
#: programs; oldest artifacts evict beyond it.
DEFAULT_STORE_BUDGET = 512 * 2 ** 20

_SUFFIX = ".sstprog"


class _CorruptArtifact(RuntimeError):
    """An artifact file that cannot be structurally parsed/verified —
    quarantined by the loader (a MISMATCHED artifact is a clean miss,
    not corruption)."""


class _VanishedArtifact(Exception):
    """An artifact that disappeared between the existence check and the
    read (a concurrent process's eviction) — a clean miss, never a
    failed search."""


def _digest(obj: Any, hexchars: int = 16) -> str:
    """Stable content digest of an already-deterministic value (frozen
    tuples, sorted items): blake2b over its repr."""
    h = hashlib.blake2b(repr(obj).encode(), digest_size=hexchars // 2)
    return h.hexdigest()




def env_fingerprint() -> Dict[str, Any]:
    """The environment identity an artifact is only valid under:
    store format, jax/jaxlib/package versions, backend platform and
    device fleet.  A mismatch in ANY field is a clean store miss (the
    jit path recompiles) — stale binaries can never execute."""
    import jaxlib

    from spark_sklearn_tpu import __version__ as _pkg_version
    devs = jax.devices()
    return {
        "format": STORE_FORMAT,
        "jax": jax.__version__,
        "jaxlib": getattr(jaxlib, "__version__", "?"),
        "package": _pkg_version,
        "platform": jax.default_backend(),
        "n_devices": len(devs),
        "device_kinds": sorted({str(d.device_kind) for d in devs}),
        "n_processes": jax.process_count(),
    }


def aval_signature(args: Tuple[Any, ...]) -> str:
    """Digest of the abstract input signature: tree structure plus
    every leaf's (shape, dtype).  Works on concrete arrays and
    ``jax.ShapeDtypeStruct`` specs alike, so the pipeline's
    compile-ahead (abstract avals) and the dispatch path (committed
    arrays) resolve the same artifact."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    sig = (str(treedef),
           tuple((tuple(np.shape(l)), str(np.dtype(l.dtype)))
                 for l in leaves))
    return _digest(sig, hexchars=12)


class ProgramStore:
    """Versioned on-disk store of AOT-serialized program artifacts.

    Layout::

        <directory>/v<STORE_FORMAT>/<env_digest>/   *.sstprog, plans.json
        <directory>/quarantine/                     corrupt artifacts

    Artifacts from other jax versions / device topologies live under
    other ``env_digest`` directories — loading them is structurally
    impossible, and each artifact's header re-states its environment so
    even a digest collision degrades to a clean miss.  Thread-safe: the
    pipeline's compile thread, the dispatch thread and supervisor
    recovery threads may all resolve programs concurrently.
    """

    def __init__(self, directory: str,
                 byte_budget: int = DEFAULT_STORE_BUDGET,
                 flight_dir: Optional[str] = None):
        self.directory = os.path.abspath(directory)
        #: where a quarantine incident's flight bundle dumps
        #: (TpuConfig.flight_dir of the activating session; the
        #: SST_FLIGHT_DIR env var still applies as the fallback)
        self.flight_dir = flight_dir
        self.env = env_fingerprint()
        self.env_digest = _digest(tuple(sorted(
            (k, tuple(v) if isinstance(v, list) else v)
            for k, v in self.env.items())), hexchars=12)
        self._dir = os.path.join(
            self.directory, f"v{STORE_FORMAT}", self.env_digest)
        os.makedirs(self._dir, exist_ok=True)
        self._lock = named_lock("programstore.ProgramStore._lock")
        self.byte_budget = int(byte_budget)
        #: deserialized artifacts resident in memory (prewarm target)
        self._mem: Dict[str, Any] = {}
        #: artifacts this process served or published — the manifest
        self._used: Dict[str, Dict[str, Any]] = {}
        self._counts = {
            "hits": 0, "misses": 0, "publishes": 0, "bytes_loaded": 0,
            "bytes_saved": 0, "quarantined": 0, "evictions": 0,
            "prewarmed": 0,
        }

    # -- naming ------------------------------------------------------------
    @staticmethod
    def entry_name(kind: str, family: str, parts_digest: str,
                   avals_digest: str) -> str:
        fam = "".join(c if c.isalnum() or c in "-_" else "_"
                      for c in str(family))[:40]
        return f"{kind}-{fam}-{parts_digest}-{avals_digest}{_SUFFIX}"

    def path_for(self, name: str) -> str:
        return os.path.join(self._dir, name)

    # -- artifact IO ---------------------------------------------------------
    def _read_artifact(self, path: str) -> Tuple[Dict[str, Any], bytes]:
        with open(path, "rb") as f:
            raw = f.read()
        if len(raw) < len(_MAGIC) + 4 or not raw.startswith(_MAGIC):
            raise _CorruptArtifact(f"{path}: bad magic")
        off = len(_MAGIC)
        hlen = int.from_bytes(raw[off:off + 4], "big")
        off += 4
        if hlen <= 0 or off + hlen > len(raw):
            raise _CorruptArtifact(f"{path}: truncated header")
        try:
            header = json.loads(raw[off:off + hlen].decode())
        except (UnicodeDecodeError, json.JSONDecodeError) as exc:
            raise _CorruptArtifact(f"{path}: unparseable header") from exc
        payload = raw[off + hlen:]
        if len(payload) != int(header.get("payload_bytes", -1)):
            raise _CorruptArtifact(f"{path}: truncated payload")
        sha = hashlib.sha256(payload).hexdigest()
        if sha != header.get("payload_sha256"):
            raise _CorruptArtifact(f"{path}: payload digest mismatch")
        return header, payload

    def _quarantine(self, path: str) -> None:
        qdir = os.path.join(self.directory, "quarantine")
        os.makedirs(qdir, exist_ok=True)
        target = os.path.join(
            qdir, f"{os.path.basename(path)}.{os.getpid()}")
        try:
            os.replace(path, target)
        except OSError as exc:
            # a concurrent loader may have quarantined it first; either
            # way the artifact is out of the serving path
            logger.debug("quarantine rename failed for %s: %r", path, exc)
        with self._lock:
            self._counts["quarantined"] += 1
        logger.warning(
            "program store: quarantined corrupt artifact %s -> %s",
            os.path.basename(path), target)
        # a quarantine is a black-box incident: something corrupted an
        # on-disk artifact — bundle the recent events for postmortem
        # (the activating config's flight_dir, else SST_FLIGHT_DIR;
        # no-op when neither is set)
        _telemetry.note_programstore("quarantine")
        _telemetry.flight_recorder().dump(
            "quarantine", flight_dir=self.flight_dir,
            context={"artifact": os.path.basename(path),
                     "moved_to": target, "store": self.directory})

    def _note_used(self, name: str, header: Dict[str, Any]) -> None:
        with self._lock:
            self._used.setdefault(name, {
                "file": name,
                "env": self.env_digest,
                "kind": header.get("kind", "?"),
                "family": header.get("family", "?"),
                "bytes": int(header.get("payload_bytes", 0)),
                "meta": dict(header.get("meta") or {}),
            })

    def load(self, name: str, kind: str = "?", family: str = "?",
             prewarm: bool = False):
        """The deserialized ``jax.export.Exported`` stored under
        ``name``, or ``None`` on a (clean) miss.  Environment mismatch
        is a miss; structural corruption quarantines the file and is a
        miss; either way the caller's jit path still runs the search."""
        t0 = time.perf_counter()
        hit_kind = "miss"
        nbytes = 0
        ex = None
        with self._lock:
            ex = self._mem.get(name)
        if ex is not None:
            hit_kind = "memory"
        else:
            path = self.path_for(name)
            if os.path.isfile(path):
                try:
                    try:
                        header, payload = self._read_artifact(path)
                    except OSError:
                        # vanished between the isfile check and the
                        # read (a concurrent publisher's eviction):
                        # clean miss, nothing to quarantine
                        raise _VanishedArtifact
                    if header.get("env") != self.env:
                        # valid artifact from another world: leave it
                        # for that world, miss here
                        header = None
                    if header is not None:
                        nbytes = len(payload)
                        try:
                            from jax import export as _jexport
                            ex = _jexport.deserialize(bytearray(payload))
                        except Exception as exc:
                            # checksummed payload jax cannot deserialize:
                            # written by a broken/foreign producer —
                            # quarantine like any other corruption
                            raise _CorruptArtifact(
                                f"{path}: deserialize failed") from exc
                        hit_kind = "disk"
                        self._note_used(name, header)
                        with self._lock:
                            self._mem[name] = ex
                except _VanishedArtifact:
                    ex = None
                except _CorruptArtifact as exc:
                    logger.warning("program store: %s", exc)
                    self._quarantine(path)
                    ex = None
        with self._lock:
            if ex is not None:
                self._counts["prewarmed" if prewarm else "hits"] += 1
                self._counts["bytes_loaded"] += nbytes
            else:
                self._counts["misses"] += 1
        _telemetry.note_programstore("hit" if ex is not None else "miss")
        t1 = time.perf_counter()
        _process.note_store("load", t0, t1, hit=ex is not None)
        get_tracer().record_span(
            "programstore.load", t0, t1, key=name,
            bytes=nbytes, hit=ex is not None, source=hit_kind,
            kind=kind, family=str(family))
        return ex

    def publish(self, name: str, exported, kind: str = "?",
                family: str = "?", meta: Optional[Dict[str, Any]] = None):
        """Serialize ``exported`` and atomically write it under
        ``name``; returns the artifact RE-deserialized from the
        published bytes (the executes-what-it-published contract — the
        loading process compiles the byte-identical module), or ``None``
        when anything fails (the caller stays on the jit path)."""
        t0 = time.perf_counter()
        try:
            blob = bytes(exported.serialize())
            header = {
                "format": STORE_FORMAT,
                "env": self.env,
                "kind": kind,
                "family": str(family),
                "payload_bytes": len(blob),
                "payload_sha256": hashlib.sha256(blob).hexdigest(),
                "meta": dict(meta or {}),
            }
            hbytes = json.dumps(header, sort_keys=True).encode()
            _atomic_write(self.path_for(name),
                          _MAGIC + len(hbytes).to_bytes(4, "big")
                          + hbytes + blob)
            self._evict_over_budget(keep=name)
            from jax import export as _jexport
            ex = _jexport.deserialize(bytearray(blob))
            self._note_used(name, header)
            with self._lock:
                self._counts["publishes"] += 1
                self._counts["bytes_saved"] += len(blob)
                self._mem[name] = ex
            _telemetry.note_programstore("publish")
            t1 = time.perf_counter()
            _process.note_store("save", t0, t1)
            get_tracer().record_span(
                "programstore.save", t0, t1, key=name,
                bytes=len(blob), kind=kind, family=str(family))
            return ex
        except Exception as exc:
            # publishing is an optimization only: a full disk, an
            # unserializable program or a deserialize bug must never
            # fail the search — the jit path produces identical results
            logger.warning(
                "program store: publish failed for %s (%r); "
                "continuing on jit", name, exc)
            return None

    def _evict_over_budget(self, keep: Optional[str] = None) -> None:
        try:
            entries = []
            for fn in os.listdir(self._dir):
                if not fn.endswith(_SUFFIX):
                    continue
                st = os.stat(os.path.join(self._dir, fn))
                entries.append((st.st_mtime, st.st_size, fn))
            total = sum(e[1] for e in entries)
            entries.sort()
            evicted = 0
            for mtime, size, fn in entries:
                if total <= self.byte_budget or fn == keep:
                    continue
                os.remove(os.path.join(self._dir, fn))
                with self._lock:
                    self._mem.pop(fn, None)
                total -= size
                evicted += 1
            if evicted:
                with self._lock:
                    self._counts["evictions"] += evicted
        except OSError as exc:
            logger.debug("program store eviction scan failed: %r", exc)

    # -- geometry plans ------------------------------------------------------
    def plan_state_path(self) -> str:
        return os.path.join(self._dir, "plans.json")

    def load_plan_state(self) -> Optional[Dict[str, Any]]:
        """The persisted geometry plan cache + cost-model state written
        by :meth:`save_plan_state`, or ``None`` (missing/corrupt —
        a fresh process simply re-plans from defaults)."""
        path = self.plan_state_path()
        if not os.path.isfile(path):
            return None
        try:
            with open(path) as f:
                return json.load(f)
        except (OSError, UnicodeDecodeError, json.JSONDecodeError) as exc:
            logger.warning(
                "program store: plan state unreadable (%r); re-planning",
                exc)
            return None

    def save_plan_state(self, state: Dict[str, Any]) -> None:
        """Atomically persist the geometry plan cache + cost-model EMA
        state next to the programs, so a fresh process plans the same
        chunk widths without re-measuring."""
        try:
            _atomic_write(self.plan_state_path(),
                          json.dumps(state).encode())
        except (OSError, TypeError, ValueError) as exc:
            # best-effort: a fresh process simply re-plans
            logger.warning(
                "program store: plan-state save failed: %r", exc)

    # -- prewarm manifest ------------------------------------------------------
    def prewarm(self, manifest: Any) -> Dict[str, Any]:
        """Load the artifacts a manifest declares into the in-memory
        cache, so a session's first search resolves its programs
        without touching disk mid-pipeline.  ``manifest`` is a path or
        an already-parsed dict; entries from other environments and
        files that have since been evicted are skipped, never errors."""
        t0 = time.perf_counter()
        if isinstance(manifest, str):
            try:
                with open(manifest) as f:
                    manifest = json.load(f)
            except (OSError, UnicodeDecodeError,
                    json.JSONDecodeError) as exc:
                logger.warning(
                    "program store: prewarm manifest unreadable (%r); "
                    "skipping prewarm", exc)
                manifest = {}
        entries = list((manifest or {}).get("entries", ()))
        loaded = skipped = 0
        nbytes = 0
        for entry in entries:
            name = os.path.basename(str(entry.get("file", "")))
            if not name.endswith(_SUFFIX) or \
                    entry.get("env") not in (None, self.env_digest):
                skipped += 1
                continue
            ex = self.load(name, kind=str(entry.get("kind", "?")),
                           family=str(entry.get("family", "?")),
                           prewarm=True)
            if ex is None:
                skipped += 1
            else:
                loaded += 1
                nbytes += int(entry.get("bytes", 0))
        summary = {"entries": len(entries), "loaded": loaded,
                   "skipped": skipped, "bytes": nbytes}
        get_tracer().record_span(
            "programstore.prewarm", t0, time.perf_counter(), **summary)
        logger.info("program store prewarm: %d/%d artifacts loaded "
                    "(%d skipped)", loaded, len(entries), skipped,
                    **summary)
        return summary

    def write_manifest(self, path: str) -> str:
        """Write the prewarm manifest of every artifact this process
        served or published — what a finished search actually used —
        for the next session's ``TpuConfig(prewarm_manifest=...)``."""
        with self._lock:
            entries = sorted(self._used.values(),
                             key=lambda e: e["file"])
        doc = {"format": STORE_FORMAT, "env": self.env,
               "env_digest": self.env_digest, "entries": entries}
        # unlike plan-state saves this propagates: the caller asked for
        # a manifest and must know it was not written
        _atomic_write(path, json.dumps(
            doc, indent=1, sort_keys=True).encode())
        return path

    # -- introspection -----------------------------------------------------
    def counts(self) -> Dict[str, int]:
        """Cumulative counter snapshot (callers diff before/after a
        search for ``search_report["programstore"]``)."""
        with self._lock:
            return dict(self._counts)

    def disk_stats(self) -> Dict[str, int]:
        """Artifact count and bytes currently resident on disk for this
        environment."""
        n = 0
        total = 0
        try:
            for fn in os.listdir(self._dir):
                if fn.endswith(_SUFFIX):
                    n += 1
                    total += os.stat(os.path.join(self._dir, fn)).st_size
        except OSError as exc:
            logger.debug("program store disk scan failed: %r", exc)
        return {"n_entries": n, "store_bytes": total}


class StoredProgram:
    """Store-backed proxy around one jitted program.

    ``resolve(*args)`` maps the call's abstract input signature to a
    callable, once per signature:

      - store HIT: the deserialized artifact wrapped in
        ``jax.jit(exported.call)`` — no python->jaxpr->HLO walk at all
        (the XLA binary comes from the persistent compilation cache,
        which saw the identical module when the artifact was
        published);
      - store MISS: ``jax.export`` traces the underlying jit program
        once, the serialized artifact is published, and THIS process
        executes the re-deserialized bytes too (so both sides of the
        store compile the same module);
      - export/publish failure: the plain jit program (identical
        results; it traces at first dispatch exactly as without the
        store).

    ``lower(*args)`` resolves first and then lowers whichever callable
    resolution produced, so the pipeline's compile-ahead
    (``parallel/pipeline.precompile``) consults the store on the
    compile thread before any lowering happens.  ``on_trace`` fires
    once per signature that actually traced (miss/fallback) — the
    search report's ``n_compiles``.
    """

    def __init__(self, jit_fn, store: ProgramStore, kind: str,
                 family: str, parts_digest: str,
                 on_trace: Optional[Callable[[], None]] = None,
                 meta: Optional[Dict[str, Any]] = None):
        self._jit = jit_fn
        self._store = store
        self._kind = str(kind)
        self._family = str(family)
        self._parts_digest = parts_digest
        self._on_trace = on_trace
        self._meta = dict(meta or {})
        self._lock = named_lock("programstore.StoredProgram._lock")
        self._resolved: Dict[str, Any] = {}

    def rebind(self, store: ProgramStore) -> None:
        """Point this (cross-search cached) proxy at the CURRENT
        :class:`ProgramStore` instance for its directory.  After a
        deactivate/re-activate cycle the singleton is a fresh object
        with fresh counters and an empty manifest record — future
        resolutions must land there, not on the dead instance (already-
        memoized signatures keep serving: same directory, same
        artifacts)."""
        if store is self._store:
            return
        with self._lock:
            self._store = store

    def resolve(self, *args):
        """The callable serving this input signature (see class
        docstring); memoized per signature."""
        sig = aval_signature(args)
        with self._lock:
            call = self._resolved.get(sig)
        if call is not None:
            return call
        name = self._store.entry_name(
            self._kind, self._family, self._parts_digest, sig)
        ex = self._store.load(name, kind=self._kind, family=self._family)
        if ex is not None:
            call = jax.jit(ex.call)
        else:
            call = None
            try:
                from jax import export as _jexport
                exported = _jexport.export(self._jit)(*args)
                published = self._store.publish(
                    name, exported, kind=self._kind, family=self._family,
                    meta=self._meta)
                if published is not None:
                    call = jax.jit(published.call)
            except Exception as exc:
                # export is an optimization only: a program jax.export
                # cannot serialize (exotic custom call, symbolic shape)
                # keeps its plain jit path — identical results, and the
                # in-process/persistent caches still apply
                logger.debug(
                    "program export failed for %s (%r); staying on jit",
                    name, exc)
            if call is None:
                call = self._jit
            if self._on_trace is not None:
                # a real trace happened (export's, or jit's at first
                # dispatch) — count it outside any lock
                self._on_trace()
        with self._lock:
            call = self._resolved.setdefault(sig, call)
        return call

    def lower(self, *args):
        """AOT seam for ``parallel/pipeline.precompile``: consult the
        store, then lower whichever callable resolution produced."""
        return self.resolve(*args).lower(*args)

    def __call__(self, *args):
        return self.resolve(*args)(*args)


# ---------------------------------------------------------------------------
# Process-global activation (mirrors dataplane.plane_for)
# ---------------------------------------------------------------------------

_STORE: Optional[ProgramStore] = None
_STORE_LOCK = named_lock("programstore._STORE_LOCK")


def _resolve_dir(config) -> Optional[str]:
    d = getattr(config, "program_store_dir", None) if config is not None \
        else None
    if not d:
        d = os.environ.get("SST_PROGRAM_STORE_DIR", "").strip() or None
    return d


def _resolve_budget(config) -> int:
    b = getattr(config, "program_store_bytes", None) if config is not None \
        else None
    if b is None:
        env = os.environ.get("SST_PROGRAM_STORE_BYTES", "").strip()
        if env:
            # a typo'd budget fails loudly at activation, not mid-search
            b = int(env)
    return DEFAULT_STORE_BUDGET if b is None else int(b)


def resolve_manifest(config) -> Optional[str]:
    """The prewarm manifest path under ``config``
    (``TpuConfig.prewarm_manifest``, else ``SST_PREWARM_MANIFEST``)."""
    m = getattr(config, "prewarm_manifest", None) if config is not None \
        else None
    if not m:
        m = os.environ.get("SST_PREWARM_MANIFEST", "").strip() or None
    return m


def activate_store(config=None) -> Optional[ProgramStore]:
    """The program store a search/session should use under ``config``
    — or ``None`` when no directory is configured
    (``TpuConfig.program_store_dir`` / ``SST_PROGRAM_STORE_DIR``), the
    byte budget disables it, or the process is part of a
    multi-controller cluster (per-host artifact stores for sharded
    programs are ROADMAP item 2 territory).  First activation for a
    directory also seeds the geometry plan cache from the persisted
    plan state."""
    directory = _resolve_dir(config)
    if not directory:
        return None
    budget = _resolve_budget(config)
    if budget <= 0:
        return None
    if jax.process_count() > 1:
        return None
    global _STORE
    fresh = False
    with _STORE_LOCK:
        if _STORE is None or \
                _STORE.directory != os.path.abspath(directory):
            _STORE = ProgramStore(
                directory, budget,
                flight_dir=getattr(config, "flight_dir", None))
            fresh = True
        else:
            _STORE.byte_budget = int(budget)
            fd = getattr(config, "flight_dir", None)
            if fd:
                # the latest activating session's flight dir wins
                _STORE.flight_dir = fd
        store = _STORE
    if fresh:
        state = store.load_plan_state()
        if state:
            from spark_sklearn_tpu.parallel.taskgrid import (
                import_plan_state)
            n = import_plan_state(state)
            logger.info("program store: seeded %d geometry plan(s) "
                        "from %s", n, store.plan_state_path())
    return store


def active_store() -> Optional[ProgramStore]:
    """The currently active store (``None`` when never activated)."""
    with _STORE_LOCK:
        return _STORE


def deactivate_store() -> None:
    """Drop the process-global store (tests; a later
    :func:`activate_store` builds a fresh one with an empty memory
    cache)."""
    global _STORE
    with _STORE_LOCK:
        _STORE = None


#: frozen-leaf types whose repr is stable across processes — a store
#: key may only be digested from these (np.generic/np.dtype reprs are
#: value-stable; arbitrary hashable objects repr their ADDRESS, which
#: would mint a key no other process can ever hit).
_STABLE_LEAVES = (str, bytes, bool, int, float, complex, type(None),
                  np.generic, np.dtype)


def _stable(frozen) -> bool:
    if isinstance(frozen, tuple):
        return all(_stable(x) for x in frozen)
    return isinstance(frozen, _STABLE_LEAVES)


def maybe_wrap(jit_fn, store: Optional[ProgramStore], parts,
               on_trace: Optional[Callable[[], None]] = None,
               meta: Optional[Dict[str, Any]] = None):
    """Wrap ``jit_fn`` in a :class:`StoredProgram` keyed by the
    deterministic ``parts`` tuple ``(kind, family, *structure)`` — or
    return it unwrapped when there is no store or the parts cannot be
    frozen deterministically (unhashable or address-repr'd captured
    objects: their digest is process-local, so a store key would never
    match across processes and would only bloat the store)."""
    if store is None:
        return jit_fn
    from spark_sklearn_tpu.parallel.taskgrid import freeze
    try:
        frozen = freeze(tuple(parts), strict=True)
    except TypeError:
        return jit_fn
    if not _stable(frozen):
        return jit_fn
    # record-only (fields=None): the store key IS the digest of every
    # structural part, so the SST_KEYCHECK log tracks which parts
    # tuples a run minted without asserting an effective-input set
    _keycheck.note("program_store", frozen, detail=str(parts[0]))
    return StoredProgram(
        jit_fn, store, kind=str(parts[0]), family=str(parts[1]),
        parts_digest=_digest(frozen), on_trace=on_trace, meta=meta)


# ---------------------------------------------------------------------------
# search_report["programstore"] block
# ---------------------------------------------------------------------------


def snapshot_counters(store: Optional[ProgramStore]) -> Dict[str, int]:
    """Counter snapshot for per-search deltas."""
    return {} if store is None else store.counts()


def report_block(store: Optional[ProgramStore],
                 before: Dict[str, int]) -> Dict[str, Any]:
    """The rendered ``search_report["programstore"]`` block (schema
    pinned in ``obs.metrics.PROGRAMSTORE_BLOCK_SCHEMA``): this search's
    store traffic plus the store's end-of-search state."""
    if store is None:
        return {"enabled": False, "hits": 0, "misses": 0, "publishes": 0,
                "bytes_loaded": 0, "bytes_saved": 0, "quarantined": 0,
                "evictions": 0, "prewarmed": 0, "n_entries": 0,
                "store_bytes": 0, "dir": ""}
    c = store.counts()
    d = store.disk_stats()
    return {
        "enabled": True,
        "hits": c["hits"] - before.get("hits", 0),
        "misses": c["misses"] - before.get("misses", 0),
        "publishes": c["publishes"] - before.get("publishes", 0),
        "bytes_loaded": c["bytes_loaded"] - before.get("bytes_loaded", 0),
        "bytes_saved": c["bytes_saved"] - before.get("bytes_saved", 0),
        "quarantined": c["quarantined"] - before.get("quarantined", 0),
        "evictions": c["evictions"] - before.get("evictions", 0),
        "prewarmed": c["prewarmed"],
        "n_entries": d["n_entries"],
        "store_bytes": d["store_bytes"],
        "dir": store.directory,
    }
