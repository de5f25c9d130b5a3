"""Lowering of the (ParameterGrid x KFold) task list onto arrays.

The reference builds `[(params, train, test) for params in grid for train,
test in cv.split(X, y)]` and ships one pickled closure per element to a Spark
executor (reference: grid_search.py _fit; call stack SURVEY §3.1).  Under XLA
the same grid must become *arrays*:

  - candidate params split into a STATIC part (changes the traced program:
    strings, bools, shape-determining ints) and a DYNAMIC part (numeric leaves
    that can batch under `vmap`).  Candidates sharing a static signature form
    one **compile group** — one XLA program, vmapped over the group.
  - folds become fixed-shape **masks** (n_folds, n_samples): 1.0 where the
    sample is in the train (resp. test) split.  Ragged train splits all get
    identical shapes this way (SURVEY §7.3 hard part #2), and every estimator
    fit is a weighted fit with the mask as sample_weight.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from spark_sklearn_tpu.obs.trace import get_tracer
from spark_sklearn_tpu.parallel.mesh import pad_to_multiple as _pad_up
from spark_sklearn_tpu.utils import keycheck as _keycheck
from spark_sklearn_tpu.utils.locks import named_lock


@dataclasses.dataclass
class CompileGroup:
    """One statically-shaped batch of candidates: a single jit program,
    vmapped over `n_candidates`."""

    static_params: Dict[str, Any]                # shared by every candidate
    dynamic_params: Dict[str, np.ndarray]        # each shape (n_candidates,)
    candidate_indices: np.ndarray                # (n_candidates,) into the
                                                 # original candidate order
    params_list: List[Dict[str, Any]]            # original dicts, group order

    @property
    def n_candidates(self) -> int:
        return len(self.candidate_indices)


def _is_dynamic_value(v: Any) -> bool:
    """A value can batch under vmap iff it is a real number that does not
    change the traced program.  Bools and ints used as sizes/switches are
    conservatively static unless the family says otherwise."""
    return isinstance(v, (float, np.floating)) and not isinstance(v, bool)


def build_compile_groups(
    candidate_params: Sequence[Mapping[str, Any]],
    dynamic_names: Optional[Sequence[str]] = None,
    dynamic_dtypes: Optional[Mapping[str, Any]] = None,
    dynamic_codes: Optional[Mapping[str, Sequence[str]]] = None,
) -> List[CompileGroup]:
    """Partition candidates into compile groups by static signature.

    `dynamic_names`: param names the estimator family promises are pure
    numeric leaves of the traced fit (e.g. C, alpha, l1_ratio, tol,
    learning_rate_init).  Anything else — and any dynamic-name whose value is
    non-numeric (e.g. C="auto") — is static for that candidate, except a
    name `dynamic_codes` lists for it (KNN's weights="distance"), which is
    dynamic as its index there.
    """
    t_span0 = time.perf_counter()
    dynamic_names = set(dynamic_names or ())
    dynamic_dtypes = dict(dynamic_dtypes or {})
    dynamic_codes = dict(dynamic_codes or {})
    groups: Dict[Tuple, Dict[str, Any]] = {}
    for idx, params in enumerate(candidate_params):
        static, dynamic = {}, {}
        for k, v in params.items():
            codes = dynamic_codes.get(k, ())
            if k in dynamic_names and isinstance(v, str) and v in codes:
                dynamic[k] = codes.index(v)
            elif k in dynamic_names and (
                _is_dynamic_value(v)
                or isinstance(v, (int, np.integer))
                and not isinstance(v, bool)
            ):
                dynamic[k] = v
            else:
                static[k] = v
        key = (
            tuple(sorted((k, _hashable(v)) for k, v in static.items())),
            tuple(sorted(dynamic)),
        )
        g = groups.setdefault(
            key, {"static": static, "dyn": {k: [] for k in dynamic},
                  "idx": [], "plist": []})
        for k, v in dynamic.items():
            g["dyn"][k].append(v)
        g["idx"].append(idx)
        g["plist"].append(dict(params))
    out = []
    for g in groups.values():
        dyn = {
            k: np.asarray(v, dtype=dynamic_dtypes.get(k, np.float32))
            for k, v in g["dyn"].items()
        }
        out.append(
            CompileGroup(
                static_params=g["static"],
                dynamic_params=dyn,
                candidate_indices=np.asarray(g["idx"], dtype=np.int64),
                params_list=g["plist"],
            )
        )
    # deterministic order: by first candidate index
    out.sort(key=lambda g: g.candidate_indices[0])
    get_tracer().record_span(
        "build_compile_groups", t_span0, time.perf_counter(),
        n_candidates=len(candidate_params), n_groups=len(out))
    return out


def pad_chunk(arr: np.ndarray, lo: int, hi: int, width: int,
              repeat: int = 1) -> np.ndarray:
    """Slice `arr[lo:hi]` and pad it to the launch's uniform `width` by
    repeating the last row, so every chunk of a compile group reuses ONE
    compiled program.  `repeat > 1` additionally repeats each row that
    many times (the task-batched layout's candidate-major fold axis).
    Pure host work: this is the "candidate stacking" phase the pipeline
    runs on its stage thread.  Writes into ONE preallocated output
    buffer (the old concatenate-then-repeat shape allocated twice per
    chunk)."""
    with get_tracer().span("pad_chunk", lo=lo, hi=hi, width=width):
        n = hi - lo
        out = np.empty((width * repeat,) + arr.shape[1:], arr.dtype)
        chunk = arr[lo:hi]
        if repeat == 1:
            out[:n] = chunk
        else:
            # candidate-major fold axis: row c lands at [c*repeat,
            # (c+1)*repeat) — identical to np.repeat(chunk, repeat, 0)
            out[:n * repeat].reshape((n, repeat) + arr.shape[1:])[:] = \
                chunk[:, None]
        if n < width:
            out[n * repeat:] = arr[hi - 1]
        return out


def split_range(lo: int, hi: int) -> Tuple[int, int, int]:
    """Bisect the candidate range [lo, hi) for OOM recovery: returns
    (lo, mid, hi) with both halves non-empty.  Callers re-pad each half
    to its own launch width via :func:`pad_chunk` — the supervisor's
    half-chunks are ordinary (narrower) chunks of the same compile
    group."""
    if hi - lo < 2:
        raise ValueError(f"range [{lo}, {hi}) cannot be bisected")
    return lo, lo + (hi - lo) // 2, hi


def freeze(v: Any, strict: bool = False):
    """Recursively hashable view of nested params/arrays.

    Shared by compile-group keying (repr fallback: grouping by repr of an
    exotic value is safe — worst case two groups that could have been
    one) and the search's cross-search program cache (`strict=True`:
    raises TypeError so unkeyable captures skip the cache instead of
    aliasing).  Object-dtype ndarrays hash by ELEMENT — ``tobytes()`` on
    them is raw PyObject pointers, and a recycled address would alias two
    different values."""
    if isinstance(v, dict):
        # key by (type, str) so {1: v} and {"1": v} freeze differently —
        # a str(k) collision would alias two distinct cache keys
        return tuple(sorted((type(k).__name__, str(k), freeze(x, strict))
                            for k, x in v.items()))
    if isinstance(v, (list, tuple)):
        return ("__seq__",) + tuple(freeze(x, strict) for x in v)
    if isinstance(v, (set, frozenset)):
        return ("__set__",) + tuple(
            sorted((freeze(x, strict) for x in v), key=repr))
    if isinstance(v, np.ndarray):
        if v.dtype == object:
            return ("__ndo__", v.shape,
                    tuple(freeze(x, strict) for x in v.ravel().tolist()))
        return ("__nd__", v.shape, str(v.dtype), v.tobytes())
    try:
        hash(v)
        return v
    except TypeError:
        if strict:
            raise
        return repr(v)


def _hashable(v: Any):
    return freeze(v)


# ---------------------------------------------------------------------------
# Waste-aware launch geometry
# ---------------------------------------------------------------------------
#
# Chunk width used to be a fixed per-group constant (pad(nc) capped by
# max_tasks_per_batch): every launch paid whatever padding that width
# implied, regardless of the measured launch overhead or per-lane fit
# cost the obs metrics already exposed (the `padding_waste` histogram).
# `plan_geometry` instead chooses each group's width from power-of-two
# buckets by minimizing
#
#     n_launches x launch_overhead  +  padded_lanes x lane_cost
#
# with the cost model fed from measured pipeline timelines
# (`GeometryCostModel.observe`).  The planner is deterministic (same
# inputs -> same plan); the engine additionally reuses the first plan
# computed for a (group structure, constraints) key in-process so a
# later search over the same shapes never recompiles at a new width
# just because the cost model drifted, and pins the chosen plan into
# the checkpoint journal so a resumed search replays the exact same
# chunk ids.


class GeometryMismatchError(RuntimeError):
    """A checkpoint's journalled launch geometry is structurally
    incompatible with the current search (different compile-group sizes
    or sorted-chunking flags): resuming would mix chunk ids across
    geometries.  Delete the checkpoint file or restore the original
    configuration (``sort_candidates`` / the candidate grid)."""


#: planner defaults before any measurement exists: ~10 ms of host-side
#: overhead per launch (dispatch + gather + finalize) and ~1 ms of
#: device compute per (candidate x fold) lane — deliberately
#: padding-averse so the cold plan never inflates a launch by more than
#: the cost of a handful of extra launches.
DEFAULT_LAUNCH_OVERHEAD_S = 0.010
DEFAULT_LANE_COST_S = 1e-3


class GeometryCostModel:
    """Measured per-launch overhead and per-lane cost, EMA-updated from
    each search's pipeline timeline (`observe`).  One process-global
    instance (:func:`geometry_cost_model`) feeds the planner."""

    def __init__(self,
                 launch_overhead_s: float = DEFAULT_LAUNCH_OVERHEAD_S,
                 lane_cost_s: float = DEFAULT_LANE_COST_S):
        #: the process-global instance is observed into at the end of
        #: every search — concurrent searches on different threads
        #: update it through this lock
        self._lock = named_lock("taskgrid.GeometryCostModel._lock")
        self.launch_overhead_s = float(launch_overhead_s)
        self.lane_cost_s = float(lane_cost_s)
        self.compile_wall_s = 0.0
        self.n_observations = 0

    def observe(self, launches,
                n_builds: Optional[int] = None) -> None:
        """Fold one search's per-launch timeline records (the
        ``search_report["pipeline"]["launches"]`` series) into the
        model.  Overhead is the MEDIAN per-launch host-side wall
        (robust to the first launch's trace+compile landing in
        dispatch_s); lane cost is total device compute over total real
        lanes; the excess dispatch over the median is recorded as the
        observed compile wall.

        ``n_builds`` — how many XLA programs were actually built behind
        this timeline slice — normalizes the compile wall to ONE
        program.  The attribution doctor prices modeled compile time as
        ``n_compiles x compile_wall_s``, so an aggregate (per-search)
        excess double-counts whenever several launches share one
        program: a scanned compile group builds one program but runs
        many chunks.  With ``n_builds=0`` the slice compiled nothing
        and its dispatch jitter is NOT folded into the compile wall at
        all.  ``None`` keeps the legacy per-slice aggregate (callers
        that cannot count builds)."""
        recs = [r for r in (launches or []) if r.get("n_tasks", 0) > 0]
        if not recs:
            return
        overheads = sorted(
            r.get("stage_wait_s", 0.0) + r.get("dispatch_s", 0.0)
            + r.get("gather_s", 0.0) + r.get("finalize_s", 0.0)
            for r in recs)
        # LOWER median: with few launches the upper median may itself
        # be a trace+compile outlier
        med_overhead = overheads[(len(overheads) - 1) // 2]
        compute = sum(r.get("compute_s", 0.0) for r in recs)
        lanes = sum(r["n_tasks"] for r in recs)
        compile_excess: Optional[float] = sum(
            max(0.0, o - med_overhead) for o in overheads)
        if n_builds is not None:
            # per-PROGRAM compile lane: divide the slice's excess over
            # the builds that caused it, or skip the EMA entirely when
            # nothing compiled (the excess is then launch jitter, not
            # compile wall)
            compile_excess = (compile_excess / n_builds
                              if n_builds > 0 else None)
        with self._lock:
            lane_cost = compute / lanes if lanes else self.lane_cost_s
            alpha = 0.5 if self.n_observations else 1.0
            self.launch_overhead_s += alpha * (
                med_overhead - self.launch_overhead_s)
            self.lane_cost_s += alpha * (lane_cost - self.lane_cost_s)
            if compile_excess is not None:
                self.compile_wall_s += alpha * (
                    compile_excess - self.compile_wall_s)
            self.n_observations += 1

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "launch_overhead_s": round(self.launch_overhead_s, 6),
                "lane_cost_s": round(self.lane_cost_s, 8),
                "compile_wall_s": round(self.compile_wall_s, 6),
                "n_observations": self.n_observations,
                "source": "measured" if self.n_observations else "default",
            }

    def state_dict(self) -> Dict[str, Any]:
        """Unrounded EMA state for cross-process persistence (the
        program store's ``plans.json``)."""
        with self._lock:
            return {
                "launch_overhead_s": self.launch_overhead_s,
                "lane_cost_s": self.lane_cost_s,
                "compile_wall_s": self.compile_wall_s,
                "n_observations": self.n_observations,
            }

    def load_state(self, state: Mapping[str, Any]) -> bool:
        """Adopt a persisted EMA state when it has seen MORE searches
        than this process — a fresh worker prices its launch geometry
        from the fleet's measured walls instead of the padding-averse
        defaults, while a process with its own (newer) measurements
        keeps them.  Returns whether the state was adopted."""
        try:
            n = int(state["n_observations"])
            overhead = float(state["launch_overhead_s"])
            lane = float(state["lane_cost_s"])
            compile_wall = float(state.get("compile_wall_s", 0.0))
        except (KeyError, TypeError, ValueError):
            return False
        if not (np.isfinite(overhead) and np.isfinite(lane)
                and overhead >= 0.0 and lane >= 0.0):
            return False
        with self._lock:
            if n <= self.n_observations:
                return False
            self.launch_overhead_s = overhead
            self.lane_cost_s = lane
            self.compile_wall_s = compile_wall
            self.n_observations = n
            return True


_COST_MODEL = GeometryCostModel()


def geometry_cost_model() -> GeometryCostModel:
    """The process-global cost model the engine observes into."""
    return _COST_MODEL


@dataclasses.dataclass
class GroupGeometry:
    """One compile group's planned launch shape."""

    group: int
    n_candidates: int
    width: int               # uniform chunk width (padded lane count / fold)
    n_chunks: int
    sorted: bool             # convergence-sorted chunking active
    #: the HBM width ceiling (memledger.width_cap) bound this group's
    #: width below the planner's cost-optimal choice.  Defaulted so
    #: pre-ledger journalled plans still deserialize.
    capped: bool = False

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class GeometryPlan:
    """The planned geometry of a whole search: per-group widths plus
    the cost-model snapshot that produced them.  Serialized verbatim
    into the checkpoint journal (``{"meta": "geometry_plan", ...}``
    line) and rendered as ``search_report["geometry"]``."""

    mode: str                              # "auto" | "fixed"
    groups: List[GroupGeometry]
    cost_model: Dict[str, Any]
    source: str = "computed"               # computed | plan-cache | journal

    def signature(self) -> Tuple:
        """Structure identity for resume-mismatch detection: the widths
        may legitimately differ across plans, the group sizes and
        sorted flags may not."""
        return tuple((g.n_candidates, g.sorted) for g in self.groups)

    def widths(self) -> List[int]:
        return [g.width for g in self.groups]

    def to_dict(self) -> Dict[str, Any]:
        return {"mode": self.mode, "source": self.source,
                "cost_model": dict(self.cost_model),
                "groups": [g.to_dict() for g in self.groups]}

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "GeometryPlan":
        return cls(
            mode=str(d.get("mode", "auto")),
            groups=[GroupGeometry(**g) for g in d.get("groups", [])],
            cost_model=dict(d.get("cost_model", {})),
            source=str(d.get("source", "computed")))

    def report_block(self) -> Dict[str, Any]:
        """The ``search_report["geometry"]`` block (schema pinned in
        ``obs.metrics.GEOMETRY_BLOCK_SCHEMA``)."""
        lanes = sum(g.n_chunks * g.width for g in self.groups)
        real = sum(g.n_candidates for g in self.groups)
        return {
            "mode": self.mode,
            "source": self.source,
            "planned_launches": sum(g.n_chunks for g in self.groups),
            "planned_waste_frac": round(
                (lanes - real) / lanes, 6) if lanes else 0.0,
            "cost_model": dict(self.cost_model),
            "groups": [g.to_dict() for g in self.groups],
        }


def _chunk_cost(nc: int, width: int, n_folds: int, overhead: float,
                lane_cost: float) -> Tuple[float, int, int]:
    """(cost, n_chunks, width) of running `nc` candidates at `width`:
    launches pay `overhead` each, padded lanes pay `lane_cost` each."""
    n_chunks = -(-nc // width)
    waste_lanes = (n_chunks * width - nc) * n_folds
    return (n_chunks * overhead + waste_lanes * lane_cost,
            n_chunks, width)


#: chunk-loop strategies: "per_chunk" dispatches one launch per chunk
#: (the default, resumable/faultable at chunk granularity); "scan"
#: rolls a compile group's chunk loop into the program via ``lax.scan``
#: so a whole scan segment executes as ONE launch.
CHUNK_LOOP_MODES = ("per_chunk", "scan")


def resolve_chunk_loop(config) -> str:
    """The search's chunk-loop strategy: ``TpuConfig.chunk_loop`` wins,
    then the ``SST_CHUNK_LOOP`` env mirror, then ``"per_chunk"`` (the
    byte-identical legacy path)."""
    mode = getattr(config, "chunk_loop", None)
    if mode is None:
        mode = os.environ.get("SST_CHUNK_LOOP", "").strip().lower() or None
    if mode is None:
        return "per_chunk"
    mode = str(mode).strip().lower()
    if mode not in CHUNK_LOOP_MODES:
        raise ValueError(
            f"chunk_loop={mode!r} is not a chunk-loop strategy; "
            f"expected one of {CHUNK_LOOP_MODES}")
    return mode


@dataclasses.dataclass(frozen=True)
class PlanKey:
    """Named-field identity of one geometry plan.

    The plan-cache key grew positionally for five PRs (min_width as a
    bolted-on 9th element, HBM width caps the 10th, the fusion lane
    discount the 11th) until every new planner input meant another
    length-gated ``j[k] if len(j) > k`` in the JSON decoder.  This
    struct names the fields; new planner inputs (``chunk_loop`` is the
    first) arrive as defaulted fields instead of positional appendage.

    Frozen + all-hashable fields, so instances key ``_PLAN_CACHE``
    directly.  :meth:`from_json` is the ONE back-compat decoder: it
    accepts both the named-dict form this process writes and the legacy
    positional list (8/9/10/11 elements) older processes persisted into
    the program store's ``plans.json``.
    """

    sizes: Tuple[int, ...]
    sorted_caps: Tuple[Optional[int], ...]
    n_folds: int
    n_task_shards: int
    max_width: int
    mode: str
    overhead_override: Optional[float]
    lane_cost_override: Optional[float]
    min_width: int = 0
    width_caps: Tuple[Optional[int], ...] = ()
    fusion_lane_discount: float = 0.0
    #: the chunk-loop strategy the plan was priced under ("per_chunk" |
    #: "scan").  Scan-mode plans cache separately — their segment
    #: planning (``plan_scan_segments``) and any future scan-aware
    #: pricing must never alias a per-chunk plan — but today's pricing
    #: is identical by construction: chunk BOUNDARIES have to match
    #: across modes so the checkpoint journal and the per-chunk OOM
    #: fallback stay chunk-id-compatible.
    chunk_loop: str = "per_chunk"
    #: per-group shared-prefix digest (None per group when the group
    #: runs the atomic pipeline path; empty tuple = planner predates
    #: prefixes / non-pipeline search).  Joins the identity so a plan
    #: priced for prefix-staged groups — whose stage-2 chunks carry a
    #: prefix-buffer dependency — never aliases an atomic plan with the
    #: same sizes, and so the journaled geometry replay
    #: (``GeometryMismatchError``) catches a resume whose prefix
    #: grouping drifted from the killed run's.
    prefix: Tuple[Optional[str], ...] = ()

    def to_json(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["sizes"] = list(self.sizes)
        d["sorted_caps"] = list(self.sorted_caps)
        d["width_caps"] = list(self.width_caps)
        d["prefix"] = list(self.prefix)
        return d

    @classmethod
    def from_json(cls, j: Any) -> "PlanKey":
        """Decode a persisted key: named dict (current) or legacy
        positional list.  Raises KeyError/IndexError/TypeError/
        ValueError on malformed records (``import_plan_state`` skips
        those)."""
        if isinstance(j, Mapping):
            return cls(
                sizes=tuple(int(x) for x in j["sizes"]),
                sorted_caps=tuple(None if c is None else int(c)
                                  for c in j["sorted_caps"]),
                n_folds=int(j["n_folds"]),
                n_task_shards=int(j["n_task_shards"]),
                max_width=int(j["max_width"]),
                mode=str(j["mode"]),
                overhead_override=(
                    None if j["overhead_override"] is None
                    else float(j["overhead_override"])),
                lane_cost_override=(
                    None if j["lane_cost_override"] is None
                    else float(j["lane_cost_override"])),
                min_width=int(j.get("min_width", 0)),
                width_caps=tuple(
                    None if c is None else int(c)
                    for c in j.get("width_caps",
                                   [None] * len(j["sizes"]))),
                fusion_lane_discount=float(
                    j.get("fusion_lane_discount", 0.0)),
                chunk_loop=str(j.get("chunk_loop", "per_chunk")),
                prefix=tuple(None if p is None else str(p)
                             for p in j.get("prefix", [])))
        # legacy positional lists, length-gated exactly as the old
        # decoder was: min_width rode in after plans.json shipped (8
        # elements = floor 0), HBM caps later still (= uncapped), the
        # fusion discount with cross-search fusion (= solo pricing)
        return cls(
            sizes=tuple(int(x) for x in j[0]),
            sorted_caps=tuple(None if c is None else int(c)
                              for c in j[1]),
            n_folds=int(j[2]), n_task_shards=int(j[3]),
            max_width=int(j[4]), mode=str(j[5]),
            overhead_override=None if j[6] is None else float(j[6]),
            lane_cost_override=None if j[7] is None else float(j[7]),
            min_width=int(j[8]) if len(j) > 8 else 0,
            width_caps=tuple(None if c is None else int(c) for c in j[9])
            if len(j) > 9 else tuple([None] * len(j[0])),
            fusion_lane_discount=float(j[10]) if len(j) > 10 else 0.0)


#: first plan computed for a (structure, constraints) key is reused for
#: the process lifetime — cost-model drift must not re-plan identical
#: searches onto new widths (each new width is a fresh XLA compile).
_PLAN_CACHE: Dict[PlanKey, GeometryPlan] = {}
_PLAN_CACHE_LOCK = named_lock("taskgrid._PLAN_CACHE_LOCK")


def plan_geometry(sizes: Sequence[int], sorted_caps: Sequence[Optional[int]],
                  n_folds: int, n_task_shards: int, max_width: int,
                  mode: str = "auto",
                  cost_model: Optional[GeometryCostModel] = None,
                  overhead_override: Optional[float] = None,
                  lane_cost_override: Optional[float] = None,
                  reuse: bool = False,
                  min_width: int = 0,
                  preferred: Optional[Sequence[Optional[int]]] = None,
                  width_caps: Optional[Sequence[Optional[int]]] = None,
                  fusion_lane_discount: float = 0.0,
                  chunk_loop: str = "per_chunk",
                  prefix: Optional[Sequence[Optional[str]]] = None,
                  ) -> GeometryPlan:
    """Choose every compile group's chunk width.

    ``sizes``: per-group candidate counts; ``sorted_caps``: per-group
    convergence-sorted width (or None when the group is unsorted) —
    sorted groups keep their graded width (the iteration-waste the
    grading removes dominates any padding trade, and the grading IS the
    family's own cost model).  Unsorted groups choose, in ``auto``
    mode, the cheapest of {the legacy zero-padding width} ∪ {power-of-
    two buckets of the task-shard count} under
    ``n_launches x overhead + padded_lanes x lane_cost``; ``fixed``
    reproduces the legacy widths exactly (the bit-compatible escape
    hatch).  Deterministic: same inputs (including the model values)
    -> same plan; ``reuse=True`` additionally serves the first plan
    computed for this structure again for the process lifetime.

    ``width_caps`` gives a per-group HBM width ceiling (the device-
    memory ledger's ``memledger.width_cap``): a capped group's width
    never exceeds it in EITHER mode — a chunk the footprint model says
    cannot fit is never planned, so OOM bisection becomes the fallback
    instead of the discovery mechanism.  Caps bound the floor and the
    preferred-width affinity too, and join the plan-cache key.

    ``fusion_lane_discount`` prices fleet-wide padding: under
    cross-search launch fusion (``serve/executor.py``) a chunk's padded
    lanes are not pure waste — a same-program peer search can fill them
    in a fused launch — so ``auto`` mode scales ``lane_cost`` by
    ``(1 - discount)``, tilting unsorted groups toward the
    fewer-launches/wider-chunks end that fusion amortizes across the
    coalesced width.  0.0 (fusion off, or solo sessions) is exact
    pre-fusion pricing, byte-identical plans.  The discount joins the
    plan-cache key, so fusion-on and fusion-off searches in one
    process never share plans.

    ``chunk_loop`` names the chunk-loop strategy the caller will run
    the plan under ("per_chunk" | "scan") and joins the plan-cache key
    as a named :class:`PlanKey` field.  It does NOT change the chosen
    widths: a scanned group's chunk boundaries must be byte-identical
    to the per-chunk path's, because the checkpoint journal addresses
    results by chunk id and a scanned segment that OOMs falls back to
    per-chunk launches over the SAME chunks.  What scan mode prices
    differently — the carry buffer and the stacked per-segment
    operands — is planned separately by :func:`plan_scan_segments`.

    ``prefix`` names each group's shared-prefix digest (None for
    atomic groups) when the caller runs a prefix-staged Pipeline
    search (``search/prefix.py``).  Like ``chunk_loop`` it does not
    change the chosen widths — suffix chunks cover the same candidate
    ranges either way — but it joins the :class:`PlanKey` so
    prefix-staged plans journal, cache and replay separately from
    atomic plans over the same sizes, and a resume whose prefix
    grouping drifted trips the journaled-geometry check.

    ``min_width`` floors every auto-chosen unsorted width (rounded up
    to the shard multiple, capped by ``max_width``) — the halving
    scheduler's ``TpuConfig.min_rung_width`` guard against
    pathologically narrow late-rung launches.  ``preferred`` gives a
    per-group already-compiled width: a valid preferred width whose
    plan cost is within the model's measured ``compile_wall_s`` of the
    optimum wins, so a mid-search re-plan (halving rung k+1) reuses
    the program compiled at rung k's width instead of paying a fresh
    trace+compile for a marginal padding saving.  Preferences are
    process-history-dependent, so a ``preferred`` plan is never cached
    (callers pass ``reuse=False``).
    """
    if mode not in ("auto", "fixed"):
        raise ValueError(
            f"geometry_mode must be 'auto' or 'fixed', got {mode!r}")
    sizes = [int(n) for n in sizes]
    sorted_caps = [None if c is None else int(c) for c in sorted_caps]
    if preferred is not None and reuse:
        raise ValueError(
            "preferred widths depend on process compile history and "
            "must not enter the plan cache; pass reuse=False")
    caps = [None] * len(sizes)
    if width_caps is not None:
        for gi, c in enumerate(width_caps):
            if c is None:
                continue
            c = int(c)
            # normalize to a launchable width: shard-multiple, at least
            # one shard stripe, never beyond the task cap
            c -= c % max(1, n_task_shards)
            caps[gi] = max(n_task_shards, min(int(max_width), c))
    fusion_lane_discount = min(1.0, max(0.0, float(fusion_lane_discount)))
    if chunk_loop not in CHUNK_LOOP_MODES:
        raise ValueError(
            f"chunk_loop must be one of {CHUNK_LOOP_MODES}, "
            f"got {chunk_loop!r}")
    prefix_key: Tuple[Optional[str], ...] = ()
    if prefix is not None:
        if len(prefix) != len(sizes):
            raise ValueError(
                f"prefix digests ({len(prefix)}) must match groups "
                f"({len(sizes)})")
        prefix_key = tuple(None if p is None else str(p) for p in prefix)
    cache_key = PlanKey(
        sizes=tuple(sizes), sorted_caps=tuple(sorted_caps),
        n_folds=int(n_folds), n_task_shards=int(n_task_shards),
        max_width=int(max_width), mode=mode,
        overhead_override=overhead_override,
        lane_cost_override=lane_cost_override,
        min_width=int(min_width), width_caps=tuple(caps),
        fusion_lane_discount=fusion_lane_discount,
        chunk_loop=str(chunk_loop), prefix=prefix_key)
    # record-only: PlanKey's named fields ARE the declared planner
    # inputs, so the SST_KEYCHECK log just tracks which plans a run
    # keyed (the toggle-a-knob tests diff these sets across configs)
    _keycheck.note("plan_key", cache_key, detail=mode)
    if reuse:
        with _PLAN_CACHE_LOCK:
            hit = _PLAN_CACHE.get(cache_key)
        if hit is not None:
            # plans seeded from the persistent program store keep their
            # provenance so search_report["geometry"] shows the fresh
            # process replayed the fleet's widths, not its own pricing
            return dataclasses.replace(
                hit, source="store" if hit.source == "store"
                else "plan-cache")

    model = cost_model or geometry_cost_model()
    overhead = (overhead_override if overhead_override is not None
                else model.launch_overhead_s)
    lane_cost = (lane_cost_override if lane_cost_override is not None
                 else model.lane_cost_s)
    # fleet-wide padding: fused peers can fill padded lanes, so they
    # price below solo waste (0.0 = exact pre-fusion costing)
    lane_cost *= (1.0 - fusion_lane_discount)
    snap = model.snapshot()
    if overhead_override is not None or lane_cost_override is not None:
        snap = {**snap, "launch_overhead_s": overhead,
                "lane_cost_s": lane_cost, "source": "override"}

    # width floor: shard-multiple, never beyond the HBM bound
    floor_w = 0
    if min_width:
        floor_w = min(max_width, _pad_up(int(min_width), n_task_shards))
    # the width-affinity allowance: a preferred (already-compiled)
    # width may cost up to this much more than the optimum before a
    # fresh compile is judged worth it.  Manual overhead/lane overrides
    # pin the geometry deterministically (tests, operators who know
    # their costs), so they zero the allowance too — otherwise a
    # measured compile wall would silently re-widen "deterministic"
    # plans.
    compile_cost = 0.0 if (overhead_override is not None
                           or lane_cost_override is not None) \
        else float(snap.get("compile_wall_s", 0.0) or 0.0)

    groups = []
    for gi, nc in enumerate(sizes):
        base_w = min(_pad_up(nc, n_task_shards), max_width)
        base_w = max(base_w, n_task_shards)
        hbm_cap = caps[gi]
        cap = sorted_caps[gi]
        if cap is not None:
            # convergence grading pins the width in both modes — the
            # HBM ceiling still bounds it (memory beats grading)
            width = cap if hbm_cap is None else min(cap, hbm_cap)
        elif mode == "fixed":
            width = base_w if hbm_cap is None else min(base_w, hbm_cap)
        else:
            # power-of-two buckets of the shard count, capped by the
            # HBM bound and by the first bucket able to hold the whole
            # group (wider would only add padding); the legacy width
            # competes too, so a zero-waste single launch is never lost
            candidates = {base_w}
            w = n_task_shards
            hold_all = _pad_up(nc, n_task_shards)
            while w <= max_width:
                candidates.add(w)
                if w >= hold_all:
                    break
                w *= 2
            if floor_w:
                candidates = {w_ for w_ in candidates if w_ >= floor_w}
                candidates.add(floor_w)
            if hbm_cap is not None:
                # the HBM ceiling wins over the min-width floor: a
                # floor the budget cannot hold would plan a chunk the
                # model already knows will not fit
                candidates = {w_ for w_ in candidates if w_ <= hbm_cap}
                candidates.add(hbm_cap)
            # total order (cost, n_chunks, width): ties prefer fewer
            # launches, then the narrower (cheaper-HBM) width
            width = min(
                sorted(candidates),
                key=lambda w_: _chunk_cost(nc, w_, n_folds, overhead,
                                           lane_cost))
            pref = preferred[gi] if preferred is not None else None
            if pref is not None:
                pref = int(pref)
                if pref >= max(n_task_shards, floor_w) \
                        and pref <= max_width \
                        and (hbm_cap is None or pref <= hbm_cap) \
                        and pref % n_task_shards == 0 and pref != width:
                    # width affinity: an already-compiled width wins
                    # when its extra plan cost is under the measured
                    # compile wall a new width would pay
                    c_pref = _chunk_cost(nc, pref, n_folds, overhead,
                                         lane_cost)[0]
                    c_opt = _chunk_cost(nc, width, n_folds, overhead,
                                        lane_cost)[0]
                    if c_pref <= c_opt + compile_cost:
                        width = pref
        groups.append(GroupGeometry(
            group=gi, n_candidates=nc, width=int(width),
            n_chunks=-(-nc // int(width)), sorted=cap is not None,
            capped=hbm_cap is not None and int(width) == hbm_cap
            and hbm_cap < base_w))
    plan = GeometryPlan(mode=mode, groups=groups, cost_model=snap)
    if reuse:
        with _PLAN_CACHE_LOCK:
            # first plan computed for a structure wins: a concurrent
            # search that raced this one keeps serving the earlier
            # entry so widths never flap mid-process
            plan = _PLAN_CACHE.setdefault(cache_key, plan)
    return plan


# ---------------------------------------------------------------------------
# Cross-process plan persistence (the program store's plans.json)
# ---------------------------------------------------------------------------
#
# The in-process plan cache pins "first plan for a structure wins" so
# cost-model drift never recompiles known shapes at new widths.  The
# program store extends that guarantee ACROSS processes: a fresh worker
# imports the persisted plans before its first search, so it requests
# the same chunk widths — and therefore the same stored AOT programs —
# the publishing process ran, instead of re-pricing from scratch.


def _plan_key_to_json(key: PlanKey) -> Dict[str, Any]:
    return key.to_json()


def _plan_key_from_json(j: Any) -> PlanKey:
    """Named-dict (current) or legacy positional-list (pre-PlanKey)
    records — :meth:`PlanKey.from_json` is the one decoder."""
    return PlanKey.from_json(j)


def export_plan_state() -> Dict[str, Any]:
    """JSON-able snapshot of the process's geometry knowledge: the plan
    cache (structure key -> chosen plan) plus the cost model's EMA
    state."""
    with _PLAN_CACHE_LOCK:
        items = list(_PLAN_CACHE.items())
    return {
        "cost_model": geometry_cost_model().state_dict(),
        "plans": [{"key": _plan_key_to_json(k), "plan": p.to_dict()}
                  for k, p in items],
    }


def import_plan_state(state: Mapping[str, Any]) -> int:
    """Seed the plan cache (and cost model) from a persisted snapshot.
    In-process plans always win (``setdefault`` — widths never flap
    mid-process); malformed records are skipped, never errors.  Returns
    how many plans were newly seeded."""
    cm = state.get("cost_model")
    if cm:
        geometry_cost_model().load_state(cm)
    n = 0
    for rec in state.get("plans", ()):
        try:
            key = _plan_key_from_json(rec["key"])
            plan = GeometryPlan.from_dict(rec["plan"])
        except (KeyError, IndexError, TypeError, ValueError):
            continue
        plan = dataclasses.replace(plan, source="store")
        with _PLAN_CACHE_LOCK:
            if _PLAN_CACHE.setdefault(key, plan) is plan:
                n += 1
    return n


def build_fold_masks(
    cv_splits: Sequence[Tuple[np.ndarray, np.ndarray]],
    n_samples: int,
    dtype=np.float32,
) -> Tuple[np.ndarray, np.ndarray]:
    """(train_idx, test_idx) pairs -> dense (n_folds, n_samples) masks.

    Reference counterpart: each Spark task slices X[train]/X[test] with ragged
    index arrays (grid_search.py -> sklearn _fit_and_score).  Fixed-shape
    masks keep every (candidate x fold) XLA program identical.
    """
    n_folds = len(cv_splits)
    train = np.zeros((n_folds, n_samples), dtype=dtype)
    test = np.zeros((n_folds, n_samples), dtype=dtype)
    for i, (tr, te) in enumerate(cv_splits):
        train[i, tr] = 1.0
        test[i, te] = 1.0
    return train, test


class StreamPlanError(RuntimeError):
    """The streaming-fold planner cannot produce a shard geometry that
    fits the HBM budget (the reserved program footprint alone exceeds
    it, or a single double-buffered row does).  Raise the budget, lower
    the chunk width (``max_tasks_per_batch``), or run ``data_mode=
    "device"`` on hardware that holds the dataset."""


@dataclasses.dataclass
class StreamPlan:
    """The planned sample-shard geometry of one streamed search.

    Like :class:`GeometryPlan` this is a *planning* artifact: the shard
    width is an analytic decision made before the first upload (budget
    minus the modeled resident program footprint, double-buffered), not
    something discovered by OOM trial-and-error.  Serialized verbatim
    into the checkpoint journal (``{"meta": "stream_plan", ...}``) so a
    resumed search replays the EXACT same shard boundaries — per-shard
    partial-statistics journal entries are only addressable under the
    geometry that wrote them."""

    n_samples: int
    shard_rows: int            # uniform rows per shard (last one padded)
    n_shards: int
    row_bytes: int             # modeled host bytes per row, all operands
    target_shard_bytes: int    # the knob that sized it (pre-cap)
    budget_bytes: int          # resolved HBM budget (0 = unbounded)
    reserved_bytes: int        # modeled non-shard resident footprint
    capped: bool = False       # True when the budget shrank the shard

    def signature(self) -> Tuple:
        """Resume identity: shard boundaries may not move between the
        journalling run and the resuming run."""
        return (int(self.n_samples), int(self.shard_rows),
                int(self.n_shards))

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "StreamPlan":
        return cls(**{k: d[k] for k in (
            "n_samples", "shard_rows", "n_shards", "row_bytes",
            "target_shard_bytes", "budget_bytes", "reserved_bytes",
            "capped") if k in d})

    def report_block(self) -> Dict[str, Any]:
        """The ``search_report["streaming"]`` planning facts (schema
        pinned in ``obs.metrics.STREAMING_BLOCK_SCHEMA``; an explicit
        literal so sstlint's schema-drift producer reads the keys)."""
        return {
            "n_samples": int(self.n_samples),
            "shard_rows": int(self.shard_rows),
            "n_shards": int(self.n_shards),
            "row_bytes": int(self.row_bytes),
            "target_shard_bytes": int(self.target_shard_bytes),
            "budget_bytes": int(self.budget_bytes),
            "reserved_bytes": int(self.reserved_bytes),
            "capped": bool(self.capped),
        }


#: headroom factor on the modeled shard residency: two staged shard
#: slabs (the pipeline's upload-ahead slot plus the one in compute)
#: never plan past budget/_STREAM_SLAB_MARGIN of the free bytes
_STREAM_SLOTS = 2
_STREAM_SLAB_MARGIN = 1.25


def plan_stream_shards(n_samples: int, row_bytes: int,
                       target_shard_bytes: int, *,
                       budget_bytes: int = 0,
                       reserved_bytes: int = 0,
                       margin: float = _STREAM_SLAB_MARGIN) -> StreamPlan:
    """Analytically size the sample shards of a streamed search.

    ``row_bytes`` is the summed host bytes of ONE row across every
    per-sample operand the engine will slice (X, y, one-hot labels,
    per-shard mask slices) — the ledger's pricing, so sparse X enters
    nnz-proportionally.  The shard width is ``target_shard_bytes``
    worth of rows, shrunk (``capped=True``) when the HBM budget minus
    the ``reserved_bytes`` program footprint cannot double-buffer two
    slabs that big.  Raises :class:`StreamPlanError` instead of
    planning a geometry the model already knows cannot fit."""
    n_samples = int(n_samples)
    row_bytes = max(1, int(row_bytes))
    target = max(1, int(target_shard_bytes))
    rows = max(1, min(n_samples, target // row_bytes))
    capped = False
    budget_bytes = int(budget_bytes or 0)
    if budget_bytes:
        free = budget_bytes - int(reserved_bytes)
        rows_budget = int(free // (_STREAM_SLOTS * row_bytes
                                   * max(1.0, float(margin))))
        if rows_budget < 1:
            raise StreamPlanError(
                "streaming-fold plan cannot fit the HBM budget: "
                f"budget={budget_bytes}B, reserved program footprint="
                f"{reserved_bytes}B leaves no room for "
                f"{_STREAM_SLOTS} x {row_bytes}B-row shard slabs; "
                "raise hbm_budget_bytes, shrink max_tasks_per_batch, "
                "or use data_mode='device'")
        if rows_budget < rows:
            rows = rows_budget
            capped = True
    rows = min(rows, n_samples)
    n_shards = -(-n_samples // rows)
    return StreamPlan(
        n_samples=n_samples, shard_rows=int(rows),
        n_shards=int(n_shards), row_bytes=int(row_bytes),
        target_shard_bytes=int(target), budget_bytes=budget_bytes,
        reserved_bytes=int(reserved_bytes), capped=bool(capped))


# ---------------------------------------------------------------------------
# Scan-segment planning (chunk_loop="scan")
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ScanSegmentPlan:
    """The planned scan-segment geometry of one compile group under
    ``chunk_loop="scan"``.

    A scanned launch stacks ``segment_len`` chunks' dynamic operands
    into one ``(segment_len, lanes, ...)`` upload and carries the
    score buffer through ``lax.scan`` — the whole slab plus the carry
    is resident for the launch's lifetime, so the segment length is an
    analytic decision against the memory ledger made BEFORE the first
    upload, like :class:`StreamPlan`'s shard width.  ``capped=True``
    records that the HBM budget split the group into more than one
    segment; a budget that cannot even hold a single-chunk segment
    plans ``segment_len=1`` rather than failing — the per-chunk OOM
    fallback (bisection, host bottom-out) takes over from there, which
    is exactly the path an OOMing scanned segment degrades to anyway.
    """

    n_chunks: int
    segment_len: int           # chunks folded into one launch
    n_segments: int
    chunk_bytes: int           # modeled slab bytes per stacked chunk
    carry_bytes: int           # modeled scan-carry residency
    budget_bytes: int          # resolved HBM budget (0 = unbounded)
    reserved_bytes: int        # modeled non-scan resident footprint
    capped: bool = False

    def segments(self) -> List[Tuple[int, int]]:
        """``[lo, hi)`` chunk-index ranges, in launch order."""
        return [(lo, min(lo + self.segment_len, self.n_chunks))
                for lo in range(0, self.n_chunks, self.segment_len)]

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


#: headroom factor on the modeled scanned-slab residency — the stacked
#: operands plus scan outputs never plan past budget/margin of the
#: free bytes (same safety style as the streaming planner's slabs)
_SCAN_SLAB_MARGIN = 1.25


def plan_scan_segments(n_chunks: int, *, chunk_bytes: int,
                       carry_bytes: int = 0,
                       budget_bytes: int = 0,
                       reserved_bytes: int = 0,
                       margin: float = _SCAN_SLAB_MARGIN
                       ) -> ScanSegmentPlan:
    """Analytically size the scan segments of a device-resident chunk
    loop.

    ``chunk_bytes`` is the summed modeled bytes ONE chunk contributes
    to a scanned launch (stacked dynamic operands + its slice of the
    stacked outputs — ``memledger.model_group_footprint``'s pricing);
    ``carry_bytes`` the scan carry (the on-device score buffer a
    halving rung accumulates for its device-resident ``top_k``);
    ``reserved_bytes`` everything already resident (data plane, masks,
    program footprint).  No budget plans ONE segment holding the whole
    group — the melt-the-launch-boundary ideal."""
    n_chunks = max(1, int(n_chunks))
    chunk_bytes = max(1, int(chunk_bytes))
    seg = n_chunks
    budget_bytes = int(budget_bytes or 0)
    if budget_bytes:
        free = (budget_bytes // max(1.0, float(margin))
                - int(reserved_bytes) - int(carry_bytes))
        seg = max(1, min(n_chunks, int(free // chunk_bytes)))
    n_segments = -(-n_chunks // seg)
    return ScanSegmentPlan(
        n_chunks=n_chunks, segment_len=int(seg),
        n_segments=int(n_segments), chunk_bytes=chunk_bytes,
        carry_bytes=int(carry_bytes), budget_bytes=budget_bytes,
        reserved_bytes=int(reserved_bytes), capped=seg < n_chunks)
