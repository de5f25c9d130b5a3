"""Metrics registry — ``search_report``'s schema pinned in one place.

Before this module the search engine hand-assembled ``search_report``
dicts in ``search/grid.py`` (and ``parallel/pipeline.py`` its
``pipeline`` block): the schema lived implicitly in a dozen mutation
sites.  Now every report key is declared once in
:data:`SEARCH_REPORT_SCHEMA` (name, kind, description), the engine
updates typed metric handles (counters / gauges / histograms / series /
structs), and the report the user reads is the registry's rendered
view — so the schema is documented from the same definitions the code
writes through (``schema_markdown()`` feeds ``docs/API.md``).

Backward compatibility contract: the rendered dict is key-for-key and
value-type compatible with the pre-registry reports; a registry in
strict mode (the default for ``search_registry``) refuses to create a
metric whose name or kind is not declared, so the schema cannot drift
silently.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Any, Dict, Iterable, Optional

__all__ = [
    "MetricDef",
    "MetricsRegistry",
    "SEARCH_REPORT_SCHEMA",
    "LAUNCH_STATS",
    "PIPELINE_BLOCK_SCHEMA",
    "FAULTS_BLOCK_SCHEMA",
    "DATAPLANE_BLOCK_SCHEMA",
    "GEOMETRY_BLOCK_SCHEMA",
    "PROGRAMSTORE_BLOCK_SCHEMA",
    "SCHEDULER_BLOCK_SCHEMA",
    "HALVING_BLOCK_SCHEMA",
    "CHUNKLOOP_BLOCK_SCHEMA",
    "PREFIX_BLOCK_SCHEMA",
    "MEMORY_BLOCK_SCHEMA",
    "STREAMING_BLOCK_SCHEMA",
    "ATTRIBUTION_BLOCK_SCHEMA",
    "PROTECTION_BLOCK_SCHEMA",
    "HEARTBEAT_BLOCK_SCHEMA",
    "PROCESS_BLOCK_SCHEMA",
    "RECOVERY_BLOCK_SCHEMA",
    "TELEMETRY_SNAPSHOT_SCHEMA",
    "search_registry",
    "schema_markdown",
]


@dataclasses.dataclass(frozen=True)
class MetricDef:
    """One declared metric: its name, kind and human description."""

    name: str
    kind: str          # counter | gauge | histogram | series | struct | label
    description: str
    #: which backends emit it ("tpu", "host", "tpu,host")
    backends: str = "tpu"
    #: a series a launch feeds: the key a family's ``launch_stats``
    #: (traced) or ``launch_facts`` (host) hook reports the value under
    stat: Optional[str] = None
    #: how a bisected chunk's launches combine it and where it is
    #: written: "max" / "sum" / "fact" (known without running) one entry
    #: a launch, "per_candidate" (a per-task vector) at the candidates'
    #: cv_results_ positions
    combine: Optional[str] = None
    #: the entry of a launch that reports solver stats and not this one
    fill: Optional[int] = None
    #: a fact of the compile group whatever a launch's width: also
    #: written, under ``stat``, on the group's ``per_group`` record
    group: bool = False


#: the pinned schema of ``BaseSearchTPU.search_report``
SEARCH_REPORT_SCHEMA = (
    MetricDef(
        "backend", "label",
        "Execution tier that ran the search: 'tpu' (compiled, the "
        "candidates x folds grid lowered onto the mesh) or 'host' "
        "(sklearn `_fit_and_score` fanned out with joblib).",
        backends="tpu,host"),
    MetricDef(
        "n_compile_groups", "gauge",
        "Number of static-signature compile groups the candidate grid "
        "partitioned into (one jitted program pair per group)."),
    MetricDef(
        "n_launches", "counter",
        "Device launches executed (fit/score/calibrate/fused chunks; "
        "resumed chunks do not launch)."),
    MetricDef(
        "n_chunks_resumed", "counter",
        "Chunks whose results were restored from the checkpoint "
        "instead of launched (TpuConfig.checkpoint_dir)."),
    MetricDef(
        "fit_wall_s", "gauge",
        "Summed device wall attributed to fitting across all launches "
        "(fused launches attribute out the calibrated score share)."),
    MetricDef(
        "score_wall_s", "gauge",
        "Summed device wall attributed to scoring across all launches, "
        "including the per-group warm calibration launch."),
    MetricDef(
        "mesh", "struct",
        "Mesh geometry the search ran on: {'task': n_task_shards, "
        "'data': n_data_shards}."),
    MetricDef(
        "per_group", "struct",
        "Per-compile-group record: static_params (repr), n_launches, "
        "fit_wall_s, score_wall_s, score_path "
        "(scan-fused/wide-fused/wide/nested), when fused chunks "
        "calibrated, score_s_per_task_calibrated, the data's n_features "
        "where the family's meta names it and, of a tree family, "
        "hist_features (of those n_features, the features a node's level "
        "histograms hold)."),
    MetricDef(
        "solver_iters_per_launch", "series",
        "Per-launch max executed solver iterations over the launch's "
        "lanes (lockstep semantics; -1 launches are omitted).",
        stat="solver_iters", combine="max"),
    MetricDef(
        "solver_iters_sum_per_launch", "series",
        "Per-launch sum of executed solver iterations over lanes "
        "(per-lane semantics for scan-sequential families).",
        stat="solver_iters_sum", combine="sum"),
    MetricDef(
        "lanes_per_launch", "series",
        "Per-launch padded lane count (candidate x fold program "
        "instances actually computed, including padding)."),
    MetricDef(
        "linesearch_one_pass_per_launch", "series",
        "Per launch of an iterative solver: 1 where the line search of "
        "glm_lbfgs_batched evaluated its trial steps through the family's "
        "one-pass evaluator (multinomial LogisticRegression, class "
        "planes), 0 where it was the generic vmap of the loss or the "
        "launch ran another solver.",
        stat="linesearch_one_pass", combine="fact", fill=0),
    MetricDef(
        "linesearch_second_pass_per_launch", "series",
        "Per launch of an iterative solver: iterations of "
        "glm_lbfgs_batched in which its staged line search evaluated the "
        "trial steps after the first four too, because some lane that was "
        "not done passed none of those; at most the launch's "
        "solver_iters_per_launch.  0 where the line search is not staged "
        "(linesearch_one_pass_per_launch reads 0).",
        stat="linesearch_second_pass", combine="max", fill=0),
    MetricDef(
        "gram_builds_per_launch", "series",
        "Per launch of a kernel-dual family (SVC, NuSVC): kernel "
        "matrices the launch built (padding included).  One for each "
        "run of candidates that share gamma and differ in C (nu) only, "
        "where the launch is made of whole runs of one length >= 2 "
        "(the 4 C of a gamma in a C x gamma grid: a quarter of the "
        "candidates); one per candidate otherwise.  Absent where a "
        "compiled Pipeline wraps the estimator (a matrix per candidate "
        "and fold there).",
        stat="gram_builds", combine="fact"),
    MetricDef(
        "dual_subproblems_per_launch", "series",
        "Per launch of a kernel-dual family: box-constrained dual "
        "subproblems advanced through _box_fista, candidates x folds x "
        "one-vs-one pairs.",
        stat="dual_subproblems", combine="fact"),
    MetricDef(
        "dual_rows_per_launch", "series",
        "Per launch of a kernel-dual family: the columns one dual's "
        "iterate holds.  2 x the class block (the largest class, rounded "
        "up to a multiple of 8) where the duals ran in the class-sorted, "
        "block-compact layout (three or more balanced classes), the "
        "number of rows where a dual is a dense row (binary problems, "
        "skewed class counts).",
        stat="dual_rows", combine="fact"),
    MetricDef(
        "dual_iters_per_candidate", "series",
        "Kernel-dual families: executed _box_fista iterations of each "
        "candidate, in cv_results_ order (the candidate's folds and "
        "pairs advance together and stop together; a count equal to the "
        "iteration cap means the cap ended the solve, not tol).  -1: the "
        "candidate was restored from a checkpoint or fitted on the "
        "host.",
        stat="dual_iters", combine="per_candidate"),
    MetricDef(
        "minibatch_steps_per_launch", "series",
        "Per launch of a minibatch family (MLPClassifier, MLPRegressor, "
        "alone or as a compiled Pipeline's final step): minibatch steps "
        "the launch executed in lockstep, its epochs x the steps of an "
        "epoch (ceil(training rows / batch_size) of the fold with the "
        "most).",
        stat="minibatch_steps", combine="max"),
    MetricDef(
        "minibatch_rows_per_launch", "series",
        "Per launch of a minibatch family: the training rows of the "
        "launch's fullest minibatch.  batch_size where a step trains on "
        "training rows only; fewer where a step's batch is padded with "
        "rows of weight zero.",
        stat="minibatch_rows", combine="max"),
    MetricDef(
        "mlp_params_per_lane", "series",
        "Per launch of a minibatch family: weights and intercepts one "
        "(candidate, fold) lane trains, from the group's "
        "hidden_layer_sizes.",
        stat="mlp_params", combine="fact"),
    MetricDef(
        "epochs_per_candidate", "series",
        "Minibatch families: epochs each candidate ran before sklearn's "
        "stopping rules or max_iter ended it (the most over its folds' "
        "first), in cv_results_ order.  -1: the candidate was restored "
        "from a checkpoint or fitted on the host.",
        stat="epochs", combine="per_candidate"),
    MetricDef(
        "tree_slots_per_launch", "series",
        "Per launch of a forest family (RandomForestClassifier, "
        "RandomForestRegressor): trees the launch executed, its forests "
        "(one a fold, shared by every candidate of the launch) x the "
        "largest n_estimators among its candidates.  (Before PR 36 a "
        "forest a lane: lanes x the largest count.)  Of a boosting "
        "family: tree_steps_per_launch x the trees of a stage.",
        stat="tree_slots", combine="sum"),
    MetricDef(
        "tree_levels_per_launch", "series",
        "Per launch of a forest family: tree levels the launch "
        "executed, tree_slots_per_launch x the group's compiled depth "
        "(a level = one partition, one histogram pass, one split and "
        "one routing of every forest).  Of a boosting family the same, "
        "of every lane.",
        stat="tree_levels", combine="sum"),
    MetricDef(
        "hist_bytes_per_lane", "series",
        "Per launch of a forest family: bytes of one forest's deepest "
        "level of (node, feature, statistic, bin) float32 histograms "
        "as the launch writes them: 2^(depth - 1) nodes x "
        "hist_features_per_node x (1 + outputs) x 256 x 4, features and "
        "statistics padded to the kernel's blocks on a TPU.  Of a "
        "boosting family: one tree's, every feature x (hessian, "
        "gradient).",
        stat="hist_bytes", combine="fact"),
    MetricDef(
        "hist_features_per_node", "series",
        "Per launch of a tree family: features a node's level "
        "histograms hold.  A forest whose max_features is a subset "
        "draws each node's own features BEFORE the level's histograms "
        "and builds those and no others (7 of 54 at covtype's width "
        "under 'sqrt'); every feature where a node may split on any (a "
        "booster, max_features = n_features).  Also on the group's "
        "per_group record, beside n_features.",
        stat="hist_features", combine="fact", group=True),
    MetricDef(
        "trees_per_candidate", "series",
        "Forest and boosting families: trees (boosting: stages) each "
        "candidate grew (its own n_estimators, capped at the grid's "
        "largest), in cv_results_ order.  -1: the candidate was "
        "restored from a checkpoint or fitted on the host.",
        stat="trees", combine="per_candidate"),
    MetricDef(
        "trees_grown_per_launch", "series",
        "Per launch of a forest family: trees the launch's loop grew, "
        "its forests (one a fold) x the trees the loop ran.  Tree t of a "
        "fold is the same tree at every n_estimators, so the launch "
        "grows it once and every candidate with a larger count reads "
        "it: the sum over a search against the sum of "
        "trees_per_candidate x folds is how many candidates a grown "
        "tree served.  Of a boosting family every executed tree is "
        "grown: tree_slots_per_launch.",
        stat="trees_grown", combine="sum"),
    MetricDef(
        "tree_steps_per_launch", "series",
        "Per launch of a boosting family (GradientBoostingClassifier, "
        "GradientBoostingRegressor): lane-stages the launch executed, "
        "its lanes (candidate x fold, padding included) x the largest "
        "n_estimators among them (lockstep: every lane is carried "
        "through the launch's longest loop).  Against the sum of "
        "trees_per_candidate x folds: the stages spent on lanes already "
        "done; none where a launch's candidates share one count.  A "
        "stage is one tree (a regressor, two classes) or a tree a "
        "class: tree_slots_per_launch counts the trees.",
        stat="tree_steps", combine="sum"),
    MetricDef(
        "padding_waste", "histogram",
        "Per-launch fraction of computed lanes that were padding "
        "(chunk tail repeated to the group's uniform width) — the "
        "price of one-compile-per-group chunking."),
    MetricDef(
        "pipeline", "struct",
        "The chunk scheduler's timeline (see the pipeline-block schema "
        "below): per-phase walls, overlap_frac, n_compiles, "
        "n_precompiled, persistent-cache traffic and the per-launch "
        "records."),
    MetricDef(
        "faults", "struct",
        "The launch supervisor's recovery record (see the faults-block "
        "schema below): retry/bisection/host-fallback/timeout counters, "
        "per-class fault counts and the per-event journal "
        "(parallel/faults.py).  On the host tier the block carries the "
        "exception that pushed the compiled tier to fall back, when "
        "one did.",
        backends="tpu,host"),
    MetricDef(
        "dataplane", "struct",
        "The device data plane's traffic during this search (see the "
        "dataplane-block schema below): cache hits/misses, bytes "
        "uploaded vs reused, staging bytes, and the plane's "
        "end-of-search state (parallel/dataplane.py)."),
    MetricDef(
        "geometry", "struct",
        "The waste-aware launch-geometry plan this search ran under "
        "(see the geometry-block schema below): per-group chunk "
        "widths, the cost model that chose them, and whether the plan "
        "was computed, served from the in-process plan cache, seeded "
        "from the persistent program store, or replayed from the "
        "checkpoint journal (parallel/taskgrid.plan_geometry)."),
    MetricDef(
        "programstore", "struct",
        "The persistent AOT program store's traffic during this "
        "search (see the programstore-block schema below): artifact "
        "hits/misses/publishes, bytes loaded vs saved, quarantines, "
        "and the store's end-of-search state "
        "(parallel/programstore.py)."),
    MetricDef(
        "scheduler", "struct",
        "The multi-tenant fair-share executor's per-search view (see "
        "the scheduler-block schema below): queue waits, interleave "
        "fraction and measured tenant shares when the search was "
        "submitted to a TpuSession's SearchExecutor; the zeroed "
        "enabled=False shape for a standalone fit "
        "(serve/executor.py)."),
    MetricDef(
        "halving", "struct",
        "Successive-halving searches only (see the halving-block "
        "schema below): per-rung candidate counts, resources, chunk "
        "widths, walls and the lanes reclaimed by mid-search "
        "geometry re-planning (search/halving.py).  Absent on "
        "exhaustive searches.",
        backends="tpu,host"),
    MetricDef(
        "chunkloop", "struct",
        "The chunk-loop mode's per-search view (see the "
        "chunkloop-block schema below): whether the device-resident "
        "scan loop ran (TpuConfig.chunk_loop='scan' / SST_CHUNK_LOOP), "
        "segments executed and chunks melted into them, launches "
        "saved, fallback reasons, and halving's device-vs-host rung "
        "elimination counts (search/grid.py scan path)."),
    MetricDef(
        "prefix", "struct",
        "The shared-prefix scheduler's per-search view (see the "
        "prefix-block schema below): whether Pipeline prefixes were "
        "staged (TpuConfig.prefix_reuse / SST_PREFIX_REUSE), distinct "
        "prefix digests vs candidates, device launches vs plane/"
        "journal re-use, recomputations saved and the recorded "
        "fallback reasons (search/prefix.py + search/grid.py stage-1 "
        "scheduler)."),
    MetricDef(
        "memory", "struct",
        "The device-memory ledger's per-search view (see the "
        "memory-block schema below): modeled per-compile-group "
        "footprints, the HBM budget/width-ceiling state, the measured "
        "watermark and the model-vs-measured error "
        "(parallel/memledger.py).  Absent when "
        "TpuConfig(memory_ledger=False) — the byte-identical "
        "pre-ledger report shape."),
    MetricDef(
        "streaming", "struct",
        "The streaming-fold data plane's per-search view (see the "
        "streaming-block schema below): the analytic shard plan "
        "(rows/shards/bytes, whether the HBM budget capped it), "
        "shards streamed vs resumed per pass, and the measured "
        "host->device bytes (search/stream.py).  Present only when "
        "the search ran with data_mode='stream'."),
    MetricDef(
        "attribution", "struct",
        "The search doctor's critical-path decomposition (see the "
        "attribution-block schema below): the measured search wall "
        "split into pinned causes (compile/stage/compute/gather/"
        "queue wait/faults/padding/memory-cap narrowing), a one-line "
        "verdict, per-rung lanes for halving searches and the "
        "regression sentinel's judgment against the run log's "
        "baseline (obs/attribution.py).  Absent when "
        "TpuConfig(attribution=False) — the byte-identical "
        "pre-doctor report shape."),
    MetricDef(
        "protection", "struct",
        "The self-protecting service's verdict for this search (see "
        "the protection-block schema below): deadline state, shed and "
        "quarantined candidates, and whether the returned cv_results_ "
        "is declared partial (parallel/faults.py protection_block).  "
        "Absent when protection is off (no search_deadline_s, "
        "partial_results='raise', admission_mode='static') — the "
        "byte-identical pre-protection report shape.",
        backends="tpu,host"),
    MetricDef(
        "heartbeat", "struct",
        "The in-flight heartbeat view for this search (see the "
        "heartbeat-block schema below): beats and steps observed, "
        "inter-beat cadence percentiles, staleness and the host-side "
        "overhead estimate (obs/heartbeat.py).  Absent when the "
        "heartbeat is off (TpuConfig.heartbeat / SST_HEARTBEAT "
        "unset) — the byte-identical beacon-less report shape."),
    MetricDef(
        "process", "struct",
        "The process ledger's view at the end of this search (see the "
        "process-block schema below): what the PROCESS paid once, up "
        "to now — the import by third-party root, the first call into "
        "the program, the first fits, and every program traced, "
        "lowered, compiled or loaded from the persistent cache, with "
        "the seconds dispatching threads waited for builds "
        "(obs/process.py).  Cumulative, not this search's delta: the "
        "block of a process's second search holds its whole set-up.",
        backends="tpu,host"),
    MetricDef(
        "n_tasks", "gauge",
        "Host tier: number of (candidate, fold) fit-and-score tasks.",
        backends="host"),
    MetricDef(
        "n_jobs", "gauge",
        "Host tier: joblib worker count the fan-out used.",
        backends="host"),
)

#: what a launch reports about its solver, by the key a family reports
#: it under: the rows above that name a ``stat``.  The one place a solver
#: counter is declared; ``search/launch.py`` carries, combines and writes
#: the values by these rows and the engine reads none of them by name.
LAUNCH_STATS = {d.stat: d for d in SEARCH_REPORT_SCHEMA if d.stat}

#: sub-keys of ``search_report["pipeline"]`` (written by
#: ``parallel.pipeline.ChunkPipeline.report`` plus the engine's cache /
#: compile counters) — documented here so the whole report schema lives
#: in one module.
PIPELINE_BLOCK_SCHEMA = (
    MetricDef("depth", "gauge",
              "Pipeline depth the search ran at (0 = synchronous)."),
    MetricDef("n_launches", "counter",
              "Launches the pipeline executed."),
    MetricDef("wall_s", "gauge", "The run's actual wall."),
    MetricDef("stage_wall_s", "gauge",
              "Sum of host staging walls (stage thread)."),
    MetricDef("dispatch_wall_s", "gauge",
              "Sum of dispatch walls (async enqueue; a first dispatch "
              "includes trace+compile)."),
    MetricDef("compute_wall_s", "gauge",
              "Sum of device-occupancy estimates."),
    MetricDef("gather_wall_s", "gauge",
              "Sum of blocking device->host transfer walls."),
    MetricDef("finalize_wall_s", "gauge",
              "Sum of result-write/checkpoint walls."),
    MetricDef("queue_wait_wall_s", "gauge",
              "Sum of multi-tenant fair-share queue waits across "
              "launches (serve/executor.py; subtracted out of "
              "dispatch_wall_s so contention never poisons the "
              "geometry cost model)."),
    MetricDef("overlap_frac", "gauge",
              "Host work hidden behind device compute, as a fraction "
              "of all host work."),
    MetricDef("n_precompiled", "counter",
              "Programs the compile thread AOT-compiled ahead of "
              "dispatch."),
    MetricDef("n_compiles", "counter",
              "Distinct traced-program constructions this search "
              "(program-cache misses)."),
    MetricDef("persistent_cache_hits", "counter",
              "Persistent XLA compilation-cache hits during this "
              "search."),
    MetricDef("persistent_cache_misses", "counter",
              "Persistent XLA compilation-cache misses during this "
              "search."),
    MetricDef("stage_bytes_total", "gauge",
              "Total host->device bytes the launches' stage phases "
              "transferred (data-plane accounting; cache hits "
              "transfer nothing and count zero)."),
    MetricDef("epoch_s", "gauge",
              "The run epoch: perf_counter timestamp of the first "
              "run() call — per-launch t0_s/t1_s (and tracer spans) "
              "are in this timebase."),
    MetricDef("launches", "series",
              "One record per launch: key, group, kind "
              "(fit/score/calibrate/fused/scan), n_tasks, n_chunks "
              "(chunks the launch served: 1 per-chunk, the segment's "
              "member count for scan), stage_bytes "
              "(host->device transfer during its stage), per-phase "
              "walls (stage_s/stage_wait_s/dispatch_s/compute_s/"
              "gather_s/finalize_s) and the launch's t0_s/t1_s "
              "window relative to the pipeline's run epoch (what the "
              "attribution analyzer slices per halving rung)."),
)

#: sub-keys of ``search_report["dataplane"]`` (written by
#: ``parallel.dataplane.report_block``) — this search's broadcast-cache
#: traffic plus the plane's end-of-search state.
DATAPLANE_BLOCK_SCHEMA = (
    MetricDef("enabled", "label",
              "Whether the device data plane was active "
              "(TpuConfig.dataplane_bytes > 0)."),
    MetricDef("hits", "counter",
              "Cache hits this search: device arrays (X/y, fold "
              "masks, tiled masks, pad zeros) reused without any "
              "host->device transfer."),
    MetricDef("misses", "counter",
              "Cache misses this search (each one uploaded or "
              "device-tiled a new resident entry)."),
    MetricDef("evictions", "counter",
              "LRU entries dropped this search to respect the byte "
              "budget."),
    MetricDef("bytes_uploaded", "gauge",
              "Host->device bytes of CACHEABLE broadcast traffic this "
              "search (X/y, fold masks, pad zeros).  Zero on a fully "
              "warm search — the acceptance signal that nothing was "
              "re-shipped."),
    MetricDef("bytes_tiled", "gauge",
              "Bytes materialized by ON-DEVICE mask tiling this "
              "search (no host->device transfer; replaces the host "
              "np.tile + upload per compile group)."),
    MetricDef("bytes_staged", "gauge",
              "Host->device bytes of per-chunk dynamic-parameter "
              "staging this search (inherently per-launch; not "
              "cacheable)."),
    MetricDef("n_entries", "gauge",
              "Entries resident in the plane after the search."),
    MetricDef("bytes_in_cache", "gauge",
              "Bytes resident in the plane after the search."),
    MetricDef("budget_bytes", "gauge",
              "The plane's byte budget (TpuConfig.dataplane_bytes)."),
    MetricDef("mask_tiling", "label",
              "How task-batched fold masks were produced: 'device' "
              "(plane-cached on-device broadcast), 'host' (legacy "
              "np.tile + upload), or 'n/a' (family does not tile)."),
)

#: sub-keys of ``search_report["geometry"]`` (written by
#: ``parallel.taskgrid.GeometryPlan.report_block``) — the launch
#: geometry the search ran under, pinned so resumes can replay it.
GEOMETRY_BLOCK_SCHEMA = (
    MetricDef("mode", "label",
              "TpuConfig.geometry_mode: 'auto' (waste-aware planner) "
              "or 'fixed' (legacy width rule)."),
    MetricDef("source", "label",
              "Where the plan came from: 'computed' (fresh), "
              "'plan-cache' (first in-process plan for this structure "
              "reused), 'store' (seeded from the persistent program "
              "store's plans.json, so a fresh process replays the "
              "publishing process's widths), or 'journal' (replayed "
              "from the checkpoint so resume reuses the exact same "
              "chunk ids)."),
    MetricDef("planned_launches", "gauge",
              "Total chunk launches the plan schedules across all "
              "compile groups."),
    MetricDef("planned_waste_frac", "gauge",
              "Fraction of planned candidate lanes that are padding "
              "(the quantity the planner minimizes against launch "
              "overhead)."),
    MetricDef("cost_model", "struct",
              "The cost-model snapshot that priced the plan: "
              "launch_overhead_s, lane_cost_s, compile_wall_s (a "
              "PER-PROGRAM build wall — observe() divides the "
              "compile excess by the launch's program-build count, "
              "so chunk_loop=\"scan\"'s coarse launches don't skew "
              "it), n_observations, source "
              "(default/measured/override)."),
    MetricDef("groups", "series",
              "Per compile group: group index, n_candidates, chosen "
              "width, n_chunks, and whether convergence-sorted "
              "chunking pinned the width."),
)

#: sub-keys of ``search_report["programstore"]`` (written by
#: ``parallel.programstore.report_block``) — this search's persistent
#: AOT-artifact traffic plus the store's end-of-search state.
PROGRAMSTORE_BLOCK_SCHEMA = (
    MetricDef("enabled", "label",
              "Whether a persistent program store was active "
              "(TpuConfig.program_store_dir / SST_PROGRAM_STORE_DIR)."),
    MetricDef("hits", "counter",
              "Programs served from serialized AOT artifacts this "
              "search — each one skipped the whole python->jaxpr->"
              "StableHLO walk.  Covering every compile group makes a "
              "cold process's n_compiles zero."),
    MetricDef("misses", "counter",
              "Store lookups that found no artifact this search (the "
              "program traced, was exported, and published for the "
              "next process)."),
    MetricDef("publishes", "counter",
              "Artifacts serialized and atomically written this "
              "search."),
    MetricDef("bytes_loaded", "gauge",
              "Artifact bytes read from disk this search (memory-"
              "cache and prewarmed hits read nothing and count "
              "zero)."),
    MetricDef("bytes_saved", "gauge",
              "Artifact bytes published this search."),
    MetricDef("quarantined", "counter",
              "Corrupt artifacts moved to the store's quarantine "
              "directory this search (each fell back to a clean jit "
              "recompile; never a failed search)."),
    MetricDef("evictions", "counter",
              "Oldest artifacts dropped this search to respect the "
              "store byte budget (TpuConfig.program_store_bytes)."),
    MetricDef("prewarmed", "counter",
              "Artifacts loaded by manifest prewarm this PROCESS "
              "(TpuSession.prewarm; cumulative, not per-search)."),
    MetricDef("n_entries", "gauge",
              "Artifacts resident on disk for this environment after "
              "the search."),
    MetricDef("store_bytes", "gauge",
              "Artifact bytes resident on disk for this environment "
              "after the search."),
    MetricDef("dir", "label",
              "The store's root directory."),
)

#: sub-keys of ``search_report["faults"]`` (written by
#: ``parallel.faults.LaunchSupervisor``) — the recovery contract's
#: observable surface, pinned next to the rest of the report schema.
FAULTS_BLOCK_SCHEMA = (
    MetricDef("retries", "counter",
              "Transient-fault retry attempts performed (exponential "
              "backoff + deterministic jitter; budgets: "
              "TpuConfig.max_launch_retries / max_search_retries)."),
    MetricDef("bisections", "counter",
              "OOM chunk bisections performed (each split relaunches "
              "the chunk as two half-width launches, lanes re-padded "
              "via parallel/taskgrid.pad_chunk)."),
    MetricDef("host_fallbacks", "counter",
              "Ranges degraded to per-candidate host execution with "
              "exact sklearn error_score semantics (bisection bottomed "
              "out, or the item had no bisect hook)."),
    MetricDef("timeouts", "counter",
              "Launches failed by the watchdog for exceeding "
              "TpuConfig.launch_timeout_s (each raises a clean "
              "LaunchTimeoutError naming the chunk and compile "
              "group)."),
    MetricDef("injected", "counter",
              "Faults injected by the deterministic fault plan "
              "(TpuConfig.fault_plan / SST_FAULT_PLAN)."),
    MetricDef("by_class", "struct",
              "Observed fault counts keyed by taxonomy class "
              "(transient/oom/hung/fatal)."),
    MetricDef("events", "series",
              "Per-event journal (bounded at 64 records): key, group, "
              "class, action (retry/recover/bisect/host_fallback/"
              "fail/raise/retries_exhausted), attempt, error."),
    MetricDef("fallback_exception", "label",
              "Host tier only: the exception type (and truncated "
              "message) that made the compiled tier fall back to the "
              "host backend, when the search started compiled."),
)


#: sub-keys of ``search_report["scheduler"]`` (written by
#: ``serve.executor.report_block`` / ``SearchExecutor.search_block``) —
#: the multi-tenant fair-share executor's per-search view.
SCHEDULER_BLOCK_SCHEMA = (
    MetricDef("enabled", "label",
              "Whether the search ran under a session's fair-share "
              "executor (TpuSession.submit / attach); False for a "
              "standalone fit, with every other key zeroed."),
    MetricDef("tenant", "label",
              "The search's tenant id (TpuConfig.tenant / SST_TENANT; "
              "'default' when unset)."),
    MetricDef("handle", "label",
              "The executor-assigned search handle id "
              "(tenant/s<sequence>)."),
    MetricDef("weight", "gauge",
              "The tenant's fair-share weight "
              "(TpuConfig.tenant_weight / SST_TENANT_WEIGHT)."),
    MetricDef("n_dispatches", "counter",
              "Chunk dispatches the search issued through the "
              "executor (queued + fastpath)."),
    MetricDef("n_fastpath", "counter",
              "Dispatches short-circuited inline because this was the "
              "only active search with empty queues — the solo-search "
              "zero-overhead path."),
    MetricDef("n_interleaved", "counter",
              "Dispatches immediately preceded on the shared dispatch "
              "stream by a DIFFERENT search's dispatch."),
    MetricDef("interleave_frac", "gauge",
              "n_interleaved / n_dispatches — > 0 proves the device "
              "stream interleaved this search's chunks with "
              "concurrent searches'."),
    MetricDef("queue_wait_s", "gauge",
              "Total time the search's chunks waited in the "
              "fair-share queue before dispatch."),
    MetricDef("queue_wait_mean_s", "gauge",
              "Mean queue wait per routed (non-fastpath) dispatch."),
    MetricDef("queue_wait_max_s", "gauge",
              "Worst single queue wait."),
    MetricDef("share_frac", "gauge",
              "This search's dispatched task-cost share of ALL cost "
              "dispatched during its active window."),
    MetricDef("tenant_shares", "struct",
              "Measured per-tenant dispatched-cost shares over this "
              "search's active window — under contention these track "
              "the configured tenant weights."),
    MetricDef("waits", "series",
              "Per routed dispatch: {tenant, wait_s} record of the "
              "seconds waited in the queue (bounded sample, tenant-"
              "stamped so merged samples from concurrent searches "
              "still attribute; bench derives PER-TENANT p50/p95 "
              "from it)."),
    MetricDef("n_fused", "counter",
              "Chunks of this search that rode a cross-search fused "
              "launch (one wide device program serving several "
              "tenants' same-program chunks).  Present only when "
              "fusion is enabled (TpuConfig.fusion / SST_FUSION)."),
    MetricDef("lanes_donated", "counter",
              "Real candidate lanes OTHER searches ran on fused "
              "launches this search led.  Present only when fusion "
              "is enabled."),
    MetricDef("lanes_borrowed", "counter",
              "Real candidate lanes this search ran on fused launches "
              "led by ANOTHER search.  Present only when fusion is "
              "enabled."),
    MetricDef("fusion_saved_launches", "counter",
              "Device launches avoided by fused launches this search "
              "led (members - 1 per fused launch).  Present only when "
              "fusion is enabled."),
)


#: sub-keys of ``search_report["halving"]`` (written by
#: ``search.halving._render_halving_block``) — the adaptive-search
#: scheduler's observable surface: what each rung cost and what lane
#: reclamation saved.
HALVING_BLOCK_SCHEMA = (
    MetricDef("enabled", "label",
              "Always True when present: the block only renders for "
              "HalvingGridSearchCV / HalvingRandomSearchCV fits."),
    MetricDef("factor", "gauge",
              "The halving factor: each rung keeps "
              "ceil(n_candidates / factor) survivors."),
    MetricDef("resource", "label",
              "The budgeted resource: 'n_samples' (fold-mask "
              "subsampling) or an estimator parameter (e.g. "
              "'n_estimators' via the masked-prefix trick)."),
    MetricDef("replan", "label",
              "Whether mid-search lane reclamation was on "
              "(TpuConfig.halving_replan): rungs re-planned into "
              "narrower chunks vs. survivors padded to rung-0 "
              "widths."),
    MetricDef("min_rung_width", "gauge",
              "The configured floor on re-planned rung widths "
              "(TpuConfig.min_rung_width; 0 = shard multiple only)."),
    MetricDef("n_rungs", "gauge",
              "Rungs executed (== n_iterations_)."),
    MetricDef("lanes_reclaimed_total", "gauge",
              "Total (candidate x fold) lanes the re-planner retired "
              "across rungs, vs. running every rung at its rung-0 "
              "chunk widths — freed device lanes instead of padding "
              "waste."),
    MetricDef("rungs", "series",
              "One record per rung: iter, n_candidates, n_resources, "
              "wall_s, widths (per compile group), "
              "n_launches_planned, n_chunks_resumed, "
              "lanes_reclaimed, padding_saved_frac, pipe_wall_s, "
              "cost_observations (the geometry cost model's "
              "observation count when the rung planned — increasing "
              "across rungs proves mid-search feedback) and "
              "launches_end (the rung's end boundary in the shared "
              "pipeline's cumulative launch timeline, consumed by "
              "the attribution analyzer's per-rung slicing)."),
)


#: sub-keys of ``search_report["chunkloop"]`` (written by
#: ``search.grid.chunkloop_block`` and mutated in place by the scan
#: finalizers and halving's elimination accounting) — the
#: device-resident chunk loop's per-search view.  Emitted for BOTH
#: loop modes: per-chunk searches report the zeroed ``enabled=False``
#: shape so the report schema never changes.
CHUNKLOOP_BLOCK_SCHEMA = (
    MetricDef("mode", "label",
              "The resolved chunk-loop mode: 'per_chunk' (default; "
              "one launch per chunk) or 'scan' "
              "(TpuConfig.chunk_loop / SST_CHUNK_LOOP)."),
    MetricDef("enabled", "label",
              "True when the scan path actually ran: mode='scan' AND "
              "the fused score path was available (the scan body is "
              "the fused program)."),
    MetricDef("n_segments", "counter",
              "Scan segments executed — each is ONE device launch "
              "serving a whole compile group (or the memory-ledger-"
              "sized slice of one)."),
    MetricDef("n_chunks_scanned", "counter",
              "Chunks melted into scan segments (journalled "
              "per chunk, so kill-resume replays at scan-segment "
              "granularity)."),
    MetricDef("n_launches_saved", "counter",
              "Launch boundaries the scan melted: sum over segments "
              "of (member chunks - 1) vs. the per-chunk path."),
    MetricDef("segment_lengths", "series",
              "Member-chunk count of each executed segment, in "
              "dispatch order."),
    MetricDef("fallbacks", "series",
              "Why (parts of) the search stayed per-chunk: "
              "'unfused-score-path' (scan requested without the fused "
              "program), 'segment-capped:<group>' (the HBM budget "
              "split the group into multiple segments), "
              "'oom-per-chunk:<group>' (an OOM on a scanned segment "
              "fell back to the per-chunk recovery path for that "
              "segment)."),
    MetricDef("rung_topk_device", "counter",
              "Halving rungs whose top-k elimination ran ON DEVICE "
              "inside the scanned launch (no score round-trip between "
              "rungs)."),
    MetricDef("rung_topk_host", "counter",
              "Halving rungs that fell back to sklearn's host _top_k "
              "(partial scan, multiple segments, or a recovered "
              "segment) while scan was enabled."),
    MetricDef("score_attribution", "label",
              "'folded' when scan melted the score launch into the "
              "segment wall (score-time columns are 0.0 and the whole "
              "wall lands in fit time); 'calibrated' on the per-chunk "
              "path (warm calibration launch splits fused walls)."),
)


#: sub-keys of ``search_report["prefix"]`` (written by
#: ``search.prefix.prefix_block``) — the shared-prefix scheduler's
#: per-search view: how many distinct Pipeline prefixes the candidate
#: grid collapsed to, how many device transforms actually launched vs
#: were re-used from the data plane or the checkpoint journal, and why
#: an eligible-looking search stayed atomic.  Emitted for EVERY search
#: (atomic searches report the zeroed ``enabled=False`` shape); a
#: halving search accumulates all rungs into this one block.
PREFIX_BLOCK_SCHEMA = (
    MetricDef("mode", "label",
              "The resolved sharing mode: 'shared' (default; distinct "
              "prefixes computed once and fanned over suffixes) or "
              "'atomic' (TpuConfig.prefix_reuse=False / "
              "SST_PREFIX_REUSE=0 — every candidate recomputes its "
              "full chain inline, the exact escape hatch)."),
    MetricDef("enabled", "label",
              "True when the prefix stage actually ran: mode='shared' "
              "AND the search passed the eligibility gate (compiled "
              "Pipeline family, dense unsharded device X, wide score "
              "path)."),
    MetricDef("n_candidates_total", "counter",
              "Pipeline candidates whose prefix the staged schedule "
              "covered (summed over halving rungs)."),
    MetricDef("n_prefixes_distinct", "counter",
              "Distinct prefix digests among those candidates — the "
              "number of transformed design matrices that exist, vs "
              "n_candidates_total the atomic path would compute."),
    MetricDef("n_prefix_launches", "counter",
              "Prefix transforms actually computed on device (one "
              "vectorized-over-folds launch each).  The headline "
              "reduction is n_candidates_total / n_prefix_launches."),
    MetricDef("n_prefix_reused", "counter",
              "Prefix stages satisfied by a live DataPlane derived "
              "buffer (zero device work; e.g. halving rungs that kept "
              "their fold masks, or a repeated search on resident "
              "data)."),
    MetricDef("n_prefix_resumed", "counter",
              "Prefix stages restored from the checkpoint journal's "
              "saved payload after a restart (one upload, no "
              "recompute)."),
    MetricDef("recompute_saved", "counter",
              "Per-candidate prefix computations the schedule avoided: "
              "n_candidates_total - n_prefix_launches."),
    MetricDef("bytes_cached", "counter",
              "Bytes of transformed (F, n, d') design matrices held "
              "as DataPlane derived buffers for this search, charged "
              "to the owning tenant."),
    MetricDef("prefix_wall_s", "gauge",
              "Wall seconds the stage-1 prefix loop spent (compute + "
              "journal writes), already excluded from per-candidate "
              "fit walls."),
    MetricDef("fallbacks", "series",
              "Why the search (or a rung) stayed atomic: "
              "'not-a-compiled-pipeline', 'no-prefix-steps', "
              "'task-batched-final', 'data-sharded', 'no-device-x', "
              "'sparse-device-data', 'nested-score', "
              "'dataplane-disabled', 'no-x-fingerprint', "
              "'undigestable-prefix'."),
)


#: sub-keys of ``search_report["memory"]`` (written by
#: ``parallel.memledger.report_block``) — the device-memory ledger's
#: per-search view: what the search modeled, what the budget allowed,
#: and what the allocator measured.
#: sub-keys of ``search_report["streaming"]`` (written by
#: ``search.stream.run_stream``) — the streamed tier's analytic shard
#: plan plus what actually crossed host->device.  The plan numbers are
#: journaled with the checkpoint (``StreamPlan``), so a resumed run
#: reports the geometry it replayed, not a recomputed one.
STREAMING_BLOCK_SCHEMA = (
    MetricDef("n_samples", "gauge",
              "Host dataset rows the streamed passes covered."),
    MetricDef("shard_rows", "gauge",
              "Planned rows per sample shard (every shard pads to "
              "this with zero-weight rows, so each pass compiles "
              "exactly one program shape per group)."),
    MetricDef("n_shards", "gauge",
              "ceil(n_samples / shard_rows) — device launches per "
              "pass."),
    MetricDef("row_bytes", "gauge",
              "Modeled host bytes one sample row contributes (data "
              "arrays + fold-mask columns)."),
    MetricDef("target_shard_bytes", "gauge",
              "The requested per-shard slab "
              "(TpuConfig.stream_shard_bytes / "
              "SST_STREAM_SHARD_BYTES)."),
    MetricDef("budget_bytes", "gauge",
              "The HBM planning budget the shard width was sized "
              "against (0 = unbudgeted: the target alone decides)."),
    MetricDef("reserved_bytes", "gauge",
              "Modeled resident program footprint (chunk operands + "
              "fold accumulators + finalized models) subtracted from "
              "the budget before sizing shards."),
    MetricDef("capped", "label",
              "True when the budget shrank the shard below the "
              "requested target — the analytic stand-in for an OOM "
              "bisection, decided before the first upload."),
    MetricDef("fit_shards_streamed", "counter",
              "Shards uploaded and folded during the fit pass (a "
              "resumed run streams only the journal's suffix)."),
    MetricDef("score_shards_streamed", "counter",
              "Shards uploaded and scored during the score pass."),
    MetricDef("fit_shards_resumed", "counter",
              "Fit-pass shards restored from the per-shard journal "
              "instead of streamed."),
    MetricDef("score_shards_resumed", "counter",
              "Score-pass shards restored from the per-shard journal "
              "instead of streamed."),
    MetricDef("h2d_bytes", "gauge",
              "Measured host->device bytes the streamed passes "
              "transferred (data-plane counter delta; fingerprint "
              "dedup makes a re-streamed shard free)."),
    MetricDef("n_live_chunks", "gauge",
              "Candidate chunks actually computed (checkpoint-"
              "resumed chunks skip both passes)."),
)


MEMORY_BLOCK_SCHEMA = (
    MetricDef("enabled", "label",
              "Always True when present: the block only renders when "
              "the ledger is on (TpuConfig.memory_ledger, default "
              "True); disabled, the report is byte-identical to the "
              "pre-ledger shape."),
    MetricDef("measured", "label",
              "Whether any local device exposes allocator "
              "memory_stats.  False (XLA:CPU) runs the ledger "
              "model-only: watermark and error stay 0."),
    MetricDef("budget_bytes", "gauge",
              "The resolved HBM planning budget "
              "(TpuConfig.hbm_budget_bytes / SST_HBM_BUDGET_BYTES; "
              "default a fraction of detected device memory, 0 = no "
              "width ceiling)."),
    MetricDef("device_limit_bytes", "gauge",
              "Smallest measured per-device allocator limit (0 when "
              "no backend reports one)."),
    MetricDef("safety_margin", "gauge",
              "The footprint model's learned over-provisioning factor "
              "— trained upward by observed OOM bisections, so the "
              "width ceiling tightens instead of repeating a bad "
              "plan."),
    MetricDef("peak_modeled_bytes", "gauge",
              "This search's largest modeled in-flight footprint: "
              "resident broadcast set + the widest chunk's modeled "
              "bytes."),
    MetricDef("resident_bytes", "gauge",
              "Modeled resident broadcast set (X/y + fold masks) this "
              "search holds on device — the data plane's share of the "
              "budget."),
    MetricDef("watermark_bytes", "gauge",
              "Measured per-device bytes-in-use high-water mark "
              "sampled at launch boundaries (0 unmeasured)."),
    MetricDef("model_error_frac", "gauge",
              "Relative error between the modeled peak and the "
              "measured watermark delta over this search (0.0 when "
              "unmeasured) — how much to trust the model."),
    MetricDef("n_samples", "counter",
              "Device memory_stats samples taken during this search "
              "(launch boundaries + telemetry sampler)."),
    MetricDef("groups", "series",
              "Per (compile group, width): modeled dyn/mask/output "
              "byte breakdown, per-candidate slope, chunk_bytes, the "
              "resident share and whether the HBM ceiling capped the "
              "planned width."),
)


#: sub-keys of ``search_report["attribution"]`` (written by
#: ``obs.attribution.attribution_block``) — the search doctor's
#: critical-path decomposition.  The lane gauges are mutually
#: exclusive seconds that sum to ``wall_s`` exactly (the analyzer
#: normalizes), so every second of a slow search is charged to one
#: pinned cause.
ATTRIBUTION_BLOCK_SCHEMA = (
    MetricDef("enabled", "label",
              "Always True when present: the block only renders when "
              "the doctor is on (TpuConfig.attribution, default "
              "True); disabled, the report is byte-identical to the "
              "pre-doctor shape."),
    MetricDef("wall_s", "gauge",
              "The measured search wall the lanes decompose (timed "
              "around the whole candidate loop, so it includes host "
              "orchestration the pipeline never sees)."),
    MetricDef("compile_s", "gauge",
              "Seconds charged to traced-program construction: "
              "summed 'compile' span walls when the search was "
              "traced, else n_compiles x the geometry cost model's "
              "compile_wall_s estimate (programs built, not chunks "
              "or launches — invariant to chunk_loop=\"scan\"'s "
              "coarser launch shape)."),
    MetricDef("stage_s", "gauge",
              "Seconds charged to host->device staging (h2d "
              "transfer) that was not hidden behind device compute."),
    MetricDef("compute_s", "gauge",
              "Seconds charged to useful device compute (padding, "
              "fault recovery and queue wait are carved out into "
              "their own lanes)."),
    MetricDef("gather_s", "gauge",
              "Seconds charged to blocking device->host result "
              "transfer."),
    MetricDef("queue_wait_s", "gauge",
              "Seconds charged to multi-tenant fair-share queue "
              "contention (serve/executor.py)."),
    MetricDef("fault_s", "gauge",
              "Seconds charged to fault recovery: retry backoff, "
              "OOM bisection relaunches and host fallbacks (summed "
              "from the recovery spans)."),
    MetricDef("padding_s", "gauge",
              "Seconds of device compute charged to padded lanes "
              "(chunk tails repeated to the group's uniform width) — "
              "compute that produced no new result."),
    MetricDef("narrowing_s", "gauge",
              "Modeled seconds of extra launch overhead caused by "
              "the HBM ceiling capping planned chunk widths "
              "(memory-block groups with capped=True)."),
    MetricDef("other_s", "gauge",
              "The wall remainder: host orchestration (chunk prep, "
              "result writes, sklearn bookkeeping) outside the "
              "pipeline's per-launch timeline."),
    MetricDef("compile_source", "label",
              "Where compile_s came from: 'traced' (compile spans in "
              "the tracer buffer) or 'modeled' (cost-model "
              "estimate)."),
    MetricDef("n_compiles", "gauge",
              "Distinct traced-program constructions the pipeline "
              "counted — the divisor behind the compile verdict."),
    MetricDef("dominant", "label",
              "The lane with the largest share of wall_s (its name "
              "minus the _s suffix) — what the verdict leads with."),
    MetricDef("verdict", "label",
              "The one-line human judgment: dominant cause, its "
              "share, and the remedy the lane implies (e.g. "
              "'compile-bound: 61% of wall in 9 traced builds; a "
              "prewarmed program store would recover ~5.2s').  When "
              "the search's chunks rode cross-search fused launches "
              "a bracketed note names the lane exchange and that "
              "per-member scatter overhead rides the gather lane."),
    MetricDef("rungs", "series",
              "Halving searches only: one record per rung — iter, "
              "wall_s and the same lane decomposition computed over "
              "the rung's slice of the launch timeline."),
    MetricDef("regression", "struct",
              "The sentinel's judgment against the run log's stored "
              "baseline: status (none/regressed/no-baseline/off), "
              "the baseline's ts/wall and per-lane deltas that "
              "breached the noise band (obs/runlog.py)."),
)


#: sub-keys of ``search_report["protection"]`` (written by
#: ``parallel.faults.protection_block``) — the self-protecting
#: service's per-search verdict.  Present only when protection is on
#: (``TpuConfig.search_deadline_s`` / ``partial_results`` /
#: ``admission_mode``); off, the report is byte-identical to the
#: pre-protection shape.
PROTECTION_BLOCK_SCHEMA = (
    MetricDef("enabled", "label",
              "Always True when present: the block only renders when "
              "the protection layer is on."),
    MetricDef("mode", "label",
              "TpuConfig.admission_mode the search ran under: "
              "'static' (slot-count admission only) or 'predictive' "
              "(ledger-modeled footprint + SLO forecast priced at "
              "submit)."),
    MetricDef("partial_results", "label",
              "TpuConfig.partial_results policy: 'raise' (deadline/"
              "persistent faults propagate) or 'best_effort' "
              "(declared-partial cv_results_)."),
    MetricDef("deadline_s", "gauge",
              "TpuConfig.search_deadline_s the search ran under (0 = "
              "no deadline)."),
    MetricDef("deadline_hit", "label",
              "Whether the deadline expired before every candidate "
              "ran."),
    MetricDef("elapsed_s", "gauge",
              "Seconds from the deadline clock's start (submit time "
              "for executor-submitted searches — queue wait counts — "
              "else fit()) to the block's rendering."),
    MetricDef("partial", "label",
              "Whether any candidate was shed or quarantined: True "
              "means cv_results_ carries error_score cells that were "
              "never run (sklearn-exact semantics) and is DECLARED "
              "partial."),
    MetricDef("n_candidates_shed", "counter",
              "Candidates written to error_score without running "
              "(deadline shedding + persistent-fault degradation)."),
    MetricDef("n_quarantined", "counter",
              "Poison candidates quarantined to error_score after K "
              "single-lane FATAL faults "
              "(TpuConfig.quarantine_fatal_k)."),
    MetricDef("shed", "series",
              "One record per shed event: chunk key, the candidate "
              "indices shed, and the reason ('deadline' or "
              "'fault')."),
    MetricDef("quarantined", "series",
              "One record per quarantined candidate: chunk key, "
              "candidate index, fault count and the final error "
              "(each also dumps a protection flight bundle)."),
    MetricDef("verdict", "label",
              "The one-line judgment: 'complete', or 'partial-' plus "
              "the causes ('deadline', 'quarantine', 'fault') that "
              "shed work."),
)


#: pinned keys of ``search_report["heartbeat"]`` — rendered by
#: ``obs.heartbeat.heartbeat_block`` only when the in-flight heartbeat
#: resolved on (``TpuConfig.heartbeat`` / ``SST_HEARTBEAT``); off, the
#: report stays byte-identical to the beacon-less shape.
HEARTBEAT_BLOCK_SCHEMA = (
    MetricDef("enabled", "label",
              "Always True when present: the block only renders when "
              "the heartbeat beacon is on."),
    MetricDef("beats_total", "counter",
              "Device beats received for this search's scanned "
              "segments (one jax.debug.callback firing per scan "
              "step)."),
    MetricDef("chunk_beats_total", "counter",
              "Cheap dispatch-time beats from the per-chunk launch "
              "path (parallel/pipeline.py note_chunk) — process-wide "
              "while the search ran."),
    MetricDef("n_segments", "counter",
              "Scan segments registered under this search's scope "
              "(live + completed)."),
    MetricDef("steps_total", "gauge",
              "Scan steps planned across the search's segments."),
    MetricDef("steps_done", "gauge",
              "Scan steps confirmed done — beats observed plus the "
              "completion clamp, so a finished search always reports "
              "steps_done == steps_total."),
    MetricDef("cadence_p50_s", "gauge",
              "Median inter-beat gap (seconds) across the search's "
              "segments — the observed per-step cost the ETA blend "
              "weighs against the geometry model's prior."),
    MetricDef("cadence_p95_s", "gauge",
              "95th-percentile inter-beat gap (seconds)."),
    MetricDef("staleness_max_s", "gauge",
              "Largest inter-beat gap observed (seconds) — what the "
              "heartbeat watchdog's timeout must exceed to avoid "
              "false HUNG verdicts."),
    MetricDef("overhead_est_s", "gauge",
              "Host seconds spent inside the beat callback for this "
              "search (locked hub update + tracer instant)."),
    MetricDef("overhead_frac", "gauge",
              "overhead_est_s over the segments' summed wall — the "
              "<2% contract tests/test_heartbeat.py enforces."),
)


#: sub-keys of ``search_report["process"]`` (rendered by
#: ``obs.process.ProcessLedger.report``; the same object from
#: ``obs.process_report()``).  Every ``*_s`` that is a point in time is
#: on ``time.perf_counter()`` relative to the first line of the
#: package's ``__init__``; every total is cumulative for the process.
PROCESS_BLOCK_SCHEMA = (
    MetricDef("import_s", "gauge",
              "Seconds from the first to the last line of "
              "`import spark_sklearn_tpu`."),
    MetricDef("import_own_s", "gauge",
              "The part of import_s spent in the package's own modules "
              "(and whatever third-party module they import that "
              "`__init__` did not import first)."),
    MetricDef("import_by_root", "struct",
              "The rest of import_s by third-party root, each charged "
              "with what its first import pulled in: numpy, jax (with "
              "jaxlib), jax.experimental.pallas, scipy, pandas (with "
              "pyarrow), sklearn."),
    MetricDef("first_call_s", "gauge",
              "When the program was first called after its import "
              "(the first `enable_persistent_cache` or `fit`); "
              "first_call_s - import_s is the caller's own time in "
              "between — under the benchmark, the TPU client's "
              "start-up.  None before any call."),
    MetricDef("fits", "series",
              "The process's first eight `fit` calls: search (the "
              "search's number), t0_s, t1_s (None while it runs)."),
    MetricDef("n_programs", "counter",
              "Programs that went through jax's back end: compiled, "
              "or loaded from the persistent cache."),
    MetricDef("n_cache_hits", "counter",
              "Of those, loaded from the persistent cache."),
    MetricDef("n_cache_misses", "counter",
              "Of those, compiled after the cache was consulted "
              "(whether or not jax then wrote an entry: a compile "
              "under the cache's thresholds writes none and is absent "
              "from the pipeline block's persistent_cache_misses)."),
    MetricDef("trace_s", "gauge",
              "Seconds of python -> jaxpr tracing, each thread's wall "
              "counted once (a trace nested in another is taken out of "
              "the outer one)."),
    MetricDef("lower_s", "gauge",
              "Seconds of jaxpr -> MLIR lowering, counted likewise.  "
              "trace_s + lower_s is all an AOT artifact store "
              "(parallel/programstore.py) could ever remove."),
    MetricDef("xla_s", "gauge",
              "Seconds inside jax's back-end compile less the cache "
              "retrieval recorded inside it: the XLA compile (and the "
              "cache write) on a miss, jax's bookkeeping on a hit; "
              "never negative.  A program-store miss or publish adds "
              "its seconds here."),
    MetricDef("cache_load_s", "gauge",
              "Seconds of persistent-cache retrieval (read, "
              "decompress, deserialize, load onto the devices), each "
              "load once; a program-store hit adds its seconds here."),
    MetricDef("build_union_s", "gauge",
              "Seconds with at least one build in flight on any "
              "thread (the union of the records' intervals)."),
    MetricDef("build_blocked_s", "gauge",
              "What building cost the wall: the `compile.wait` spans "
              "(a dispatching thread joined a build on sst-compile) "
              "plus the seconds of every build that ran on a thread "
              "inside `fit` other than sst-compile."),
    MetricDef("builds", "series",
              "The 64 longest records, by t0_s: name (jax's fun_name, "
              "or programstore.load / .save), label (the `compile` "
              "span's, where the build ran under one), thread, search, "
              "t0_s, t1_s, trace_s, lower_s, cache_load_s, xla_s, cache "
              "(hit / miss / off), blocking.  The totals above stay "
              "exact when records are dropped."),
)


#: pinned keys of the telemetry snapshot's ``recovery`` block — the
#: crash-safe service's counters (``serve/journal.py``: durable
#: submission WAL under ``TpuConfig.service_journal_dir`` /
#: ``SST_SERVICE_JOURNAL_DIR``, lease fencing, warm restart).  The
#: zeroed shape renders when no journal is configured.
RECOVERY_BLOCK_SCHEMA = (
    MetricDef("journal_entries_total", "counter",
              "Verified WAL records the restart scan read from the "
              "service journal."),
    MetricDef("nonterminal_found_total", "counter",
              "Journaled searches whose last transition was "
              "non-terminal at restart — what the warm restart owed "
              "the caller."),
    MetricDef("recovered_total", "counter",
              "Searches re-admitted through TpuSession.resubmit() "
              "(fingerprint-verified, checkpoint journal replayed)."),
    MetricDef("mismatch_total", "counter",
              "Resubmissions refused because the re-bound data's "
              "blake2b fingerprint did not match the journaled one "
              "(RecoveryDataMismatchError)."),
    MetricDef("lease_takeovers_total", "counter",
              "Stale leases fenced: the previous owner was dead (or "
              "silent past service_lease_timeout_s) and this process "
              "took the journal directory over."),
    MetricDef("lease_conflicts_total", "counter",
              "Lease acquisitions refused because a LIVE owner held a "
              "fresh stamp (ServiceLeaseError)."),
    MetricDef("unclean_shutdowns_total", "counter",
              "Takeovers that implied the previous owner died without "
              "release_lease — each dumps a crash-marker flight "
              "bundle."),
    MetricDef("time_to_recover_s", "gauge",
              "Seconds from this process's journal scan to its first "
              "successful resubmit — the operator-facing warm-restart "
              "latency."),
)


#: top-level keys of ``TpuSession.telemetry_snapshot()`` — the fleet
#: telemetry service's JSON view (``obs/telemetry.py``), also served
#: as ``/snapshot.json`` (and rendered to Prometheus text) by the
#: session's localhost endpoint (``obs/fleet.py``,
#: ``TpuConfig.telemetry_port`` / ``SST_TELEMETRY_PORT``).
TELEMETRY_SNAPSHOT_SCHEMA = (
    MetricDef("enabled", "label",
              "Whether the telemetry service is aggregating; the "
              "zeroed shape renders when it is off."),
    MetricDef("ts_unix_s", "gauge",
              "Wall-clock timestamp the snapshot was rendered at."),
    MetricDef("window_s", "gauge",
              "Sliding-window span (seconds) the rates and "
              "percentiles below cover."),
    MetricDef("interval_s", "gauge",
              "Sampler-thread tick period (seconds)."),
    MetricDef("n_samples", "counter",
              "Sampler ticks since the service enabled."),
    MetricDef("tenants", "struct",
              "Per-tenant SLO series: dispatches/tasks/queue-wait "
              "cumulative totals plus sliding-window queue-wait "
              "p50/p95, throughput (task units per second) and "
              "share_frac — these agree with the searches' own "
              "search_report['scheduler'] blocks."),
    MetricDef("device", "struct",
              "Device occupancy over the window: busy seconds (from "
              "per-launch compute estimates) and occupancy_frac."),
    MetricDef("scheduler", "struct",
              "Dispatch-loop view: cumulative dispatches, loop busy "
              "seconds and idle fraction over the window, plus the "
              "sampler's polled queue depth and active/pending search "
              "counts."),
    MetricDef("dataplane", "struct",
              "Host->device transfer totals and window rate, plus the "
              "sampler's polled plane state (hits/misses/residency; "
              "per-tenant residency lands under tenants)."),
    MetricDef("programstore", "struct",
              "AOT-store hit/miss/publish/quarantine event totals "
              "plus the sampler's polled cumulative counters."),
    MetricDef("memory", "struct",
              "Device-memory view: per-device bytes-in-use / limit / "
              "pressure (sampled from jax memory_stats where the "
              "backend provides it), the ledger's modeled peak, "
              "measured watermark, safety margin and a bounded recent "
              "max-pressure series — these agree with the searches' "
              "search_report['memory'] blocks."),
    MetricDef("faults", "struct",
              "Observed fault totals by taxonomy class and recovery "
              "action (fed by the launch supervisor's event hook)."),
    MetricDef("regression", "struct",
              "The cross-run regression sentinel's latest judgment "
              "(obs/runlog.py): checks/flagged totals, the last "
              "run's status and the lanes that breached the noise "
              "band — also rendered as the sst_regression_* "
              "Prometheus family."),
    MetricDef("protection", "struct",
              "The self-protecting service's process totals: "
              "admission decisions (admitted/queued/rejected, by "
              "reason), candidates shed, poison candidates "
              "quarantined and deadline expiries — also rendered as "
              "the sst_protection_* Prometheus family."),
    MetricDef("fusion", "struct",
              "Cross-search launch-fusion totals: fused launches, "
              "member chunks, saved launches, real vs padded lanes, "
              "and the per-tenant lane exchange (lanes borrowed on "
              "peers' launches / donated to peers) — also rendered "
              "as the sst_fusion_* Prometheus family."),
    MetricDef("recovery", "struct",
              "Crash-safe service totals (serve/journal.py): WAL "
              "entries scanned, non-terminal searches found and "
              "recovered at warm restart, fingerprint mismatches, "
              "lease fencing verdicts and time-to-recover — keys "
              "pinned in RECOVERY_BLOCK_SCHEMA, also rendered as the "
              "sst_recovery_* Prometheus family."),
    MetricDef("flight", "struct",
              "Flight-recorder state: records seen, ring occupancy, "
              "black-box bundles dumped."),
    MetricDef("heartbeat", "struct",
              "In-flight heartbeat totals (beats, chunk beats, "
              "segments, cadence/staleness) plus every live search "
              "handle's steps_done/steps_total progress and blended "
              "ETA — also rendered as the sst_heartbeat_* Prometheus "
              "family and tools/fleet_top.py's progress column."),
)


class Counter:
    """Monotonically increasing integer metric."""

    __slots__ = ("_data", "name")

    def __init__(self, data, name):
        self._data = data
        self.name = name

    def inc(self, n: int = 1) -> None:
        self._data[self.name] += n

    @property
    def value(self) -> int:
        return self._data[self.name]


class Gauge:
    """Point-in-time numeric metric (settable and accumulable)."""

    __slots__ = ("_data", "name")

    def __init__(self, data, name):
        self._data = data
        self.name = name

    def set(self, v) -> None:
        self._data[self.name] = v

    def add(self, v) -> None:
        self._data[self.name] += v

    @property
    def value(self):
        return self._data[self.name]


class Label(Gauge):
    """String-valued metric (e.g. the backend name)."""

    __slots__ = ()


class Histogram:
    """Streaming summary of observations, rendered as a plain dict
    {count, sum, mean, min, max} so the report stays JSON-able."""

    __slots__ = ("_data", "name")

    def __init__(self, data, name):
        self._data = data
        self.name = name

    def observe(self, v: float) -> None:
        h = self._data[self.name]
        v = float(v)
        h["count"] += 1
        h["sum"] += v
        h["min"] = v if h["min"] is None else min(h["min"], v)
        h["max"] = v if h["max"] is None else max(h["max"], v)
        h["mean"] = h["sum"] / h["count"]

    @property
    def value(self) -> Dict[str, Any]:
        return self._data[self.name]


_KIND_DEFAULTS = {
    "counter": lambda: 0,
    "gauge": lambda: 0.0,
    "label": lambda: "",
    "series": list,
    "struct": dict,
    "histogram": lambda: {"count": 0, "sum": 0.0, "mean": 0.0,
                          "min": None, "max": None},
}

_KIND_HANDLES = {
    "counter": Counter,
    "gauge": Gauge,
    "label": Label,
    "histogram": Histogram,
}


class MetricsRegistry:
    """Named metrics writing into one ordered dict (``.data``).

    ``.data`` is the live rendered view: handing it to a consumer (the
    ``search_report`` property) costs nothing and stays current as the
    engine updates metrics mid-run.  In strict mode every metric must
    be declared in the schema with a matching kind — the pin that stops
    report drift.
    """

    def __init__(self, schema: Optional[Iterable[MetricDef]] = None,
                 strict: Optional[bool] = None):
        self._defs = {d.name: d for d in (schema or ())}
        self._strict = bool(self._defs) if strict is None else strict
        self.data: "OrderedDict[str, Any]" = OrderedDict()
        self._handles: Dict[str, Any] = {}

    # -- declaration / lookup -------------------------------------------
    def _resolve(self, name: str, kind: str):
        d = self._defs.get(name)
        if d is None:
            if self._strict:
                raise KeyError(
                    f"metric {name!r} is not declared in this registry's "
                    "schema; add a MetricDef before writing it")
        elif d.kind != kind:
            raise TypeError(
                f"metric {name!r} is declared as a {d.kind}, not a {kind}")
        if name not in self.data:
            self.data[name] = _KIND_DEFAULTS[kind]()

    def _handle(self, name: str, kind: str):
        h = self._handles.get(name)
        if h is None:
            self._resolve(name, kind)
            h = self._handles[name] = _KIND_HANDLES[kind](self.data, name)
        return h

    def counter(self, name: str) -> Counter:
        return self._handle(name, "counter")

    def gauge(self, name: str) -> Gauge:
        return self._handle(name, "gauge")

    def label(self, name: str) -> Label:
        return self._handle(name, "label")

    def histogram(self, name: str) -> Histogram:
        return self._handle(name, "histogram")

    def series(self, name: str) -> list:
        """The named append-only list itself (per-launch records)."""
        self._resolve(name, "series")
        return self.data[name]

    def struct(self, name: str) -> dict:
        """The named nested-dict value itself (mesh, per_group, ...)."""
        self._resolve(name, "struct")
        return self.data[name]

    def put(self, name: str, value) -> None:
        """Assign a struct wholesale (e.g. the pipeline block computed
        by ChunkPipeline.report())."""
        self._resolve(name, "struct")
        self.data[name] = value

    # -- rendering -------------------------------------------------------
    def render(self) -> Dict[str, Any]:
        """Plain-dict snapshot (shallow; series/struct values are the
        live containers — copy before mutating)."""
        return dict(self.data)

    def describe(self) -> Iterable[MetricDef]:
        return tuple(self._defs.values())


def search_registry(backend: str) -> MetricsRegistry:
    """A strict registry pre-declared with the search_report schema,
    with the backend label already set (always the first key)."""
    reg = MetricsRegistry(SEARCH_REPORT_SCHEMA)
    reg.label("backend").set(backend)
    return reg


def schema_markdown() -> str:
    """The search_report schema as a markdown section — the single
    source `docs/API.md` renders (dev/build_api_docs.py)."""
    out = [
        "## `search_report` schema\n",
        "\nRendered from `spark_sklearn_tpu.obs.metrics."
        "SEARCH_REPORT_SCHEMA` — the same definitions the engine "
        "writes through, so this table cannot drift from the code.\n",
        "\n| key | kind | backend | description |\n",
        "|---|---|---|---|\n",
    ]
    for d in SEARCH_REPORT_SCHEMA:
        out.append(
            f"| `{d.name}` | {d.kind} | {d.backends} | "
            f"{d.description} |\n")
    out.append(
        "\n### What a launch reports\n\nThe series above that a launch "
        "feeds, by the key a family reports the value under "
        "(`Family.launch_stats`, traced, and `Family.launch_facts`, on "
        "the host; `models/base.py`).  `combine` is how the values of a "
        "bisected chunk's launches become one; `fill` is the entry of a "
        "launch that reports solver stats and not this one.\n"
        "\n| stat | combine | fill | series |\n|---|---|---|---|\n")
    for d in LAUNCH_STATS.values():
        out.append(f"| `{d.stat}` | {d.combine} | "
                   f"{'' if d.fill is None else d.fill} | `{d.name}` |\n")
    out.append("\n### `search_report[\"pipeline\"]` block\n")
    out.append("\n| key | kind | description |\n|---|---|---|\n")
    for d in PIPELINE_BLOCK_SCHEMA:
        out.append(f"| `{d.name}` | {d.kind} | {d.description} |\n")
    out.append("\n### `search_report[\"faults\"]` block\n")
    out.append("\n| key | kind | description |\n|---|---|---|\n")
    for d in FAULTS_BLOCK_SCHEMA:
        out.append(f"| `{d.name}` | {d.kind} | {d.description} |\n")
    out.append("\n### `search_report[\"dataplane\"]` block\n")
    out.append("\n| key | kind | description |\n|---|---|---|\n")
    for d in DATAPLANE_BLOCK_SCHEMA:
        out.append(f"| `{d.name}` | {d.kind} | {d.description} |\n")
    out.append("\n### `search_report[\"geometry\"]` block\n")
    out.append("\n| key | kind | description |\n|---|---|---|\n")
    for d in GEOMETRY_BLOCK_SCHEMA:
        out.append(f"| `{d.name}` | {d.kind} | {d.description} |\n")
    out.append("\n### `search_report[\"programstore\"]` block\n")
    out.append("\n| key | kind | description |\n|---|---|---|\n")
    for d in PROGRAMSTORE_BLOCK_SCHEMA:
        out.append(f"| `{d.name}` | {d.kind} | {d.description} |\n")
    out.append("\n### `search_report[\"scheduler\"]` block\n")
    out.append("\n| key | kind | description |\n|---|---|---|\n")
    for d in SCHEDULER_BLOCK_SCHEMA:
        out.append(f"| `{d.name}` | {d.kind} | {d.description} |\n")
    out.append("\n### `search_report[\"halving\"]` block\n")
    out.append(
        "\nPresent only on `HalvingGridSearchCV` / "
        "`HalvingRandomSearchCV` fits (`search/halving.py`).\n")
    out.append("\n| key | kind | description |\n|---|---|---|\n")
    for d in HALVING_BLOCK_SCHEMA:
        out.append(f"| `{d.name}` | {d.kind} | {d.description} |\n")
    out.append("\n### `search_report[\"chunkloop\"]` block\n")
    out.append(
        "\nThe device-resident chunk loop's per-search view "
        "(`TpuConfig.chunk_loop=\"scan\"` / `SST_CHUNK_LOOP`; "
        "`search/grid.py`).  Always present on compiled-tier "
        "searches — per-chunk runs report the zeroed "
        "`enabled=False` shape.\n")
    out.append("\n| key | kind | description |\n|---|---|---|\n")
    for d in CHUNKLOOP_BLOCK_SCHEMA:
        out.append(f"| `{d.name}` | {d.kind} | {d.description} |\n")
    out.append("\n### `search_report[\"prefix\"]` block\n")
    out.append(
        "\nThe shared-prefix scheduler's per-search view "
        "(`TpuConfig.prefix_reuse` / `SST_PREFIX_REUSE`, default on; "
        "`search/prefix.py` + the `search/grid.py` stage-1 "
        "scheduler).  Always present on compiled-tier searches — "
        "atomic runs report the zeroed `enabled=False` shape.\n")
    out.append("\n| key | kind | description |\n|---|---|---|\n")
    for d in PREFIX_BLOCK_SCHEMA:
        out.append(f"| `{d.name}` | {d.kind} | {d.description} |\n")
    out.append("\n### `search_report[\"memory\"]` block\n")
    out.append(
        "\nPresent when the device-memory ledger is on "
        "(`TpuConfig.memory_ledger`, default True; "
        "`parallel/memledger.py`).\n")
    out.append("\n| key | kind | description |\n|---|---|---|\n")
    for d in MEMORY_BLOCK_SCHEMA:
        out.append(f"| `{d.name}` | {d.kind} | {d.description} |\n")
    out.append("\n### `search_report[\"streaming\"]` block\n")
    out.append(
        "\nPresent only when the search ran the streaming-fold data "
        "plane (`TpuConfig.data_mode=\"stream\"` / `SST_DATA_MODE`; "
        "`search/stream.py`).\n")
    out.append("\n| key | kind | description |\n|---|---|---|\n")
    for d in STREAMING_BLOCK_SCHEMA:
        out.append(f"| `{d.name}` | {d.kind} | {d.description} |\n")
    out.append("\n### `search_report[\"attribution\"]` block\n")
    out.append(
        "\nPresent when the search doctor is on "
        "(`TpuConfig.attribution`, default True; "
        "`obs/attribution.py`).  The lane gauges sum to `wall_s` "
        "exactly.\n")
    out.append("\n| key | kind | description |\n|---|---|---|\n")
    for d in ATTRIBUTION_BLOCK_SCHEMA:
        out.append(f"| `{d.name}` | {d.kind} | {d.description} |\n")
    out.append("\n### `search_report[\"protection\"]` block\n")
    out.append(
        "\nPresent when the self-protecting service is on "
        "(`TpuConfig.search_deadline_s` / `partial_results` / "
        "`admission_mode`; `parallel/faults.py`).\n")
    out.append("\n| key | kind | description |\n|---|---|---|\n")
    for d in PROTECTION_BLOCK_SCHEMA:
        out.append(f"| `{d.name}` | {d.kind} | {d.description} |\n")
    out.append("\n### `search_report[\"heartbeat\"]` block\n")
    out.append(
        "\nPresent when the in-flight heartbeat beacon is on "
        "(`TpuConfig.heartbeat` / `SST_HEARTBEAT`; "
        "`obs/heartbeat.py`).\n")
    out.append("\n| key | kind | description |\n|---|---|---|\n")
    for d in HEARTBEAT_BLOCK_SCHEMA:
        out.append(f"| `{d.name}` | {d.kind} | {d.description} |\n")
    out.append("\n### `search_report[\"process\"]` block\n")
    out.append(
        "\nWhat the process paid once, up to the end of this search "
        "(`obs/process.py`); always present, on both tiers.  An "
        "operator reads the same object from "
        "`spark_sklearn_tpu.obs.process_report()` at any time: \"why "
        "did my first search take a minute\" is `import_s`, "
        "`first_call_s - import_s`, `cache_load_s` / `xla_s` and "
        "`build_blocked_s`, and the records of `builds` with "
        "`blocking` true.\n")
    out.append("\n| key | kind | description |\n|---|---|---|\n")
    for d in PROCESS_BLOCK_SCHEMA:
        out.append(f"| `{d.name}` | {d.kind} | {d.description} |\n")
    out.append("\n### telemetry `recovery` block\n")
    out.append(
        "\nThe crash-safe service's counters "
        "(`spark_sklearn_tpu/serve/journal.py`: durable submission "
        "WAL under `TpuConfig.service_journal_dir` / "
        "`SST_SERVICE_JOURNAL_DIR`, lease fencing, warm restart) — "
        "the `recovery` key of the telemetry snapshot, zeroed when no "
        "journal is configured.\n")
    out.append("\n| key | kind | description |\n|---|---|---|\n")
    for d in RECOVERY_BLOCK_SCHEMA:
        out.append(f"| `{d.name}` | {d.kind} | {d.description} |\n")
    out.append("\n### `TpuSession.telemetry_snapshot()` / fleet "
               "endpoint schema\n")
    out.append(
        "\nTop-level keys of the fleet-telemetry snapshot "
        "(`spark_sklearn_tpu/obs/telemetry.py`), served as "
        "`/snapshot.json` and rendered to Prometheus text by the "
        "session's localhost endpoint.\n")
    out.append("\n| key | kind | description |\n|---|---|---|\n")
    for d in TELEMETRY_SNAPSHOT_SCHEMA:
        out.append(f"| `{d.name}` | {d.kind} | {d.description} |\n")
    return "".join(out)
