"""Cache-key surfaces + the opt-in runtime key-flow recorder.

The engine's headline contract — bit-exact ``cv_results_`` across the
program cache, the persistent program store, cross-search launch
fusion, scan segments, prefix reuse and kill-resume — rests on one
invariant: *everything that influences a traced program must join the
key that caches it*.  :data:`KEY_SURFACES` is the single declared map
of those key surfaces; two consumers build on it:

  - ``tools/sstlint`` (the ``keyflow`` checker) loads this module
    import-light (no jax) and statically proves, per registered
    surface, that every ``TpuConfig`` read reaching a traced closure
    flows into the matching key (``key-part-missing``) and that no key
    part is dead weight nobody reads (``key-part-dead``);
  - under ``SST_KEYCHECK=1`` (mirroring ``SST_LOCKCHECK``) the
    surfaces call :func:`note` at each key construction, recording the
    ACTUAL key tuples per compiled artifact.  Two distinct traced
    artifacts colliding on one key — the aliasing bug class PRs 15/17/
    19 each fixed by hand — fails the suite via the conftest hook, and
    the per-surface key log lets tests prove that toggling a declared
    key-feeding knob really changes the recorded key.

Off (the default) :func:`note` is a single env check: zero overhead,
zero behavior change.  This module must stay stdlib-only so the
linter can execute it without paying the jax import.
"""

from __future__ import annotations

import hashlib
import os
import threading
from typing import Any, Dict, Mapping, Optional, Tuple

__all__ = [
    "KEY_SURFACES",
    "KeyFlowRecorder",
    "get_recorder",
    "keycheck_enabled",
    "note",
    "registry_markdown",
]

#: Every cache-key surface in the engine, keyed by surface name.  Per
#: entry:
#:
#:   - ``relpath``: package-relative module that constructs the key;
#:   - ``anchor``: the function that builds/consumes key tuples there
#:     (the static pass resolves call sites / definitions by this
#:     name, and ``keycheck-note-missing`` requires the module to call
#:     ``note("<surface>", ...)``);
#:   - ``config_fields``: the ``TpuConfig`` fields DECLARED
#:     key-feeding at this surface.  The static pass holds the key
#:     expressions to this list in both directions: a declared field
#:     absent from the key is ``key-part-missing``, an undeclared
#:     ``config.*`` key part that no traced path reads is
#:     ``key-part-dead``;
#:   - ``key_tokens``: per declared field, the LOCAL NAME that carries
#:     its value into key expressions when the raw ``config.<field>``
#:     attribute does not appear there (``hb`` in grid);
#:   - ``aliases``: store-key identifier -> the in-memory-key
#:     identifier carrying the same information (``mesh_desc`` ->
#:     ``mesh``), for the store-parts-vs-key consistency check;
#:   - ``dataflow``: True when the surface's call sites pair a key
#:     tuple with a resolvable traced callable, letting the static
#:     pass additionally prove read-implies-keyed over the closure.
KEY_SURFACES: Dict[str, Dict[str, Any]] = {
    "program_cache": {
        "relpath": "search/grid.py",
        "anchor": "_cached_program",
        "description": (
            "the cross-search in-memory cache of jitted programs "
            "(fit/score/fused/scan/prefix), keyed by everything the "
            "per-search closures capture"),
        "config_fields": ("bf16_matmul", "heartbeat"),
        "key_tokens": {"heartbeat": "hb"},
        "aliases": {"mesh_desc": "mesh",
                    "store_score_names": "score_key",
                    "store_sw_key": "sw_blind"},
        "dataflow": True,
    },
    "program_store": {
        "relpath": "parallel/programstore.py",
        "anchor": "maybe_wrap",
        "description": (
            "the persistent AOT program store's deterministic "
            "(kind, family, *structure) key parts, digested "
            "cross-process; the parts tuples are CONSTRUCTED at the "
            "program_cache call sites, whose store-parts-vs-key "
            "consistency check covers their contents"),
        "config_fields": (),
        "dataflow": False,
    },
    "fuse_spec": {
        "relpath": "search/schedule.py",
        "anchor": "make_fuse_spec",
        "description": (
            "cross-search launch fusion: equal keys guarantee members "
            "share one compiled fused program and resident buffers"),
        "config_fields": ("bf16_matmul",),
        "dataflow": False,
    },
    "checkpoint": {
        "relpath": "search/grid.py",
        "anchor": "fingerprint",
        "description": (
            "the checkpoint journal fingerprint: a resumed search may "
            "only reuse chunks computed under a result-identical "
            "config"),
        "config_fields": ("bf16_matmul", "dtype"),
        "dataflow": False,
    },
    "plan_key": {
        "relpath": "parallel/taskgrid.py",
        "anchor": "plan_geometry",
        "description": (
            "the geometry plan cache: PlanKey's named fields are the "
            "declared planner inputs, decoded back-compat from "
            "plans.json"),
        "config_fields": ("chunk_loop",),
        "dataflow": False,
    },
    "dataplane": {
        "relpath": "parallel/dataplane.py",
        "anchor": "derived",
        "description": (
            "derived device buffers (e.g. prefix-transformed "
            "matrices) cached by content key parts; equal keys must "
            "mean equal bytes"),
        "config_fields": (),
        "dataflow": False,
    },
}


def keycheck_enabled() -> bool:
    """Is the runtime key-flow recorder active (``SST_KEYCHECK=1``)?
    Read at each :func:`note` call so tests may flip it mid-process."""
    return os.environ.get("SST_KEYCHECK", "").strip().lower() in (
        "1", "true", "on", "yes")


def _digest(obj: Any) -> str:
    """Stable-within-process 16-hex digest of an arbitrary key part
    (repr-based: key tuples may hold meshes, families and other
    rich objects whose reprs are stable for the process lifetime)."""
    return hashlib.sha256(repr(obj).encode(
        "utf-8", "backslashreplace")).hexdigest()[:16]


class KeyFlowRecorder:
    """Accumulates (surface, key) -> artifact-signature observations.

    A *collision* is one (surface, key) observed with two different
    signatures: two distinct traced artifacts would alias one cache
    slot — exactly the bug class the declared key surfaces exist to
    prevent.  Signatures are the site's *effective trace inputs*
    (``fields``); surfaces that cannot name one record key-only lines
    (no collision check, but the key log still feeds the
    toggle-a-knob-changes-the-key tests)."""

    def __init__(self):
        # the recorder is lint/lockcheck META-infrastructure, like the
        # lock shim's own mutex: a named lock here would make the
        # SST_LOCKCHECK recorder observe the SST_KEYCHECK recorder
        self._mu = threading.Lock()  # sstlint: disable=unnamed-lock
        #: (surface, key_digest) -> first observation
        self.by_key: Dict[Tuple[str, str], Dict[str, Any]] = {}
        self.collisions: list = []
        #: surface -> set of observed key digests
        self.keys_by_surface: Dict[str, set] = {}
        self.n_notes = 0

    def note(self, surface: str, key: Any,
             fields: Optional[Mapping[str, Any]] = None,
             detail: str = "") -> None:
        kd = _digest(key)
        sig = _digest(tuple(sorted(
            (str(k), repr(v)) for k, v in fields.items()))) \
            if fields is not None else None
        with self._mu:
            self.n_notes += 1
            self.keys_by_surface.setdefault(surface, set()).add(kd)
            prev = self.by_key.get((surface, kd))
            if prev is None:
                self.by_key[(surface, kd)] = {
                    "sig": sig,
                    "sigs": {sig},
                    "fields": dict(fields) if fields is not None
                    else None,
                    "detail": detail,
                }
            elif sig is not None and prev["sig"] is not None \
                    and sig not in prev["sigs"]:
                # one report per distinct aliasing signature, however
                # many launches repeat the same collision
                prev["sigs"].add(sig)
                self.collisions.append({
                    "surface": surface,
                    "key_digest": kd,
                    "fields_a": prev["fields"],
                    "detail_a": prev["detail"],
                    "fields_b": dict(fields),
                    "detail_b": detail,
                })

    def keys(self, surface: str) -> frozenset:
        """Observed key digests of one surface (the toggle-knob tests
        compare these across reconfigured runs)."""
        with self._mu:
            return frozenset(self.keys_by_surface.get(surface, ()))

    def report(self) -> Dict[str, Any]:
        with self._mu:
            return {
                "n_notes": self.n_notes,
                "n_keys": len(self.by_key),
                "keys_by_surface": {
                    s: len(v)
                    for s, v in sorted(self.keys_by_surface.items())},
                "collisions": list(self.collisions),
            }

    def reset(self) -> None:
        with self._mu:
            self.by_key.clear()
            self.collisions.clear()
            self.keys_by_surface.clear()
            self.n_notes = 0


_RECORDER = KeyFlowRecorder()


def get_recorder() -> KeyFlowRecorder:
    """The process-global recorder every instrumented surface reports
    to (tests may construct private :class:`KeyFlowRecorder`\\ s)."""
    return _RECORDER


def note(surface: str, key: Any,
         fields: Optional[Mapping[str, Any]] = None,
         detail: str = "") -> None:
    """Record one key construction when ``SST_KEYCHECK=1`` — a single
    env read otherwise, so the hooks cost nothing in production."""
    if keycheck_enabled():
        _RECORDER.note(surface, key, fields=fields, detail=detail)


def registry_markdown() -> str:
    """The key-surface registry table ``dev/build_api_docs.py``
    renders into ``docs/API.md``."""
    out = [
        "## Cache-key surfaces (`utils/keycheck.py`)\n",
        "\nEvery cache-key surface, with its declared key-feeding "
        "`TpuConfig` fields — held to the code by the `keyflow` "
        "rules in `tools/sstlint` and by the `SST_KEYCHECK=1` "
        "runtime recorder.\n",
        "\n| surface | module | anchor | declared key-feeding "
        "fields |\n|---|---|---|---|\n",
    ]
    for name in sorted(KEY_SURFACES):
        s = KEY_SURFACES[name]
        fields = ", ".join(f"`{f}`" for f in s["config_fields"]) \
            or "—"
        out.append(f"| `{name}` | `{s['relpath']}` | "
                   f"`{s['anchor']}` | {fields} |\n")
    return "".join(out)
