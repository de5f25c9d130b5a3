"""Span-name vocabulary — the single source of truth for trace names.

Every span, instant and async track the engine records is declared
here, so the three consumers can never drift from each other:

  - the instrumentation sites (``tracer.span("...")`` across the
    package) are linted against this table by ``tools/sstlint``'s
    ``span-unknown-name`` rule — a typo'd or ad-hoc span name fails the
    static-analysis gate instead of silently fragmenting the timeline;
  - ``tools/trace_summary.py`` aggregates exported traces with the
    same table (async spans group by their registered prefix) and
    warns on names it has never heard of;
  - ``dev/build_api_docs.py`` renders the vocabulary into
    ``docs/API.md`` so the trace names users grep for are documented
    from the definitions the code records through.

This module is deliberately import-light (stdlib only): trace_summary
loads it by file path so digesting a trace never pays the jax import.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

__all__ = [
    "SpanDef",
    "SPAN_VOCABULARY",
    "ASYNC_PREFIXES",
    "KNOWN_TRACKS",
    "MIRRORED_SPANS",
    "known_scope_names",
    "known_span_names",
    "async_prefix",
    "is_known_span",
    "vocabulary_markdown",
]


class SpanDef(NamedTuple):
    """One registered trace name.

    ``kind``: "span" (complete X event), "instant" (zero-duration
    marker), "async" (b/e pair on a virtual track; ``name`` is the
    PREFIX — the recorded name may append an identifier, e.g.
    ``launch g0c1:fused``), or "scope" (a ``jax.named_scope`` inside a
    compiled program: it names device operations in the profiler's
    trace and records nothing on the host).

    ``layer``: the layer of ``PERF.md`` section 3 the name belongs to.

    ``mirror``: while a ``jax.profiler`` trace is being recorded the
    span is also written into it, as the host event ``sst.<name>`` on
    the device operations' own clock (``obs/trace.py``).  Set on the
    spans that bound one piece of host work; the spans that enclose a
    whole search, rung or scan segment stay out, since they cover every
    idle gap inside them whole and would take every gap's name.
    """

    name: str
    kind: str
    module: str
    description: str
    layer: str = ""
    mirror: bool = False


#: the registered vocabulary, grouped by recording module.
SPAN_VOCABULARY: Tuple[SpanDef, ...] = (
    # search/grid.py
    SpanDef("search.fit", "span", "search.grid",
            "One whole GridSearchCV/RandomizedSearchCV fit.",
            layer="search API"),
    SpanDef("prevalidate", "span", "search.grid",
            "Candidate-param constraint validation before any launch.",
            layer="search API", mirror=True),
    SpanDef("refit", "span", "search.grid",
            "The best_estimator_ refit after the sweep.",
            layer="search API", mirror=True),
    SpanDef("host.fit_and_score", "span", "search.grid",
            "Host-tier per-candidate sklearn _fit_and_score fan-out.",
            layer="search API", mirror=True),
    SpanDef("geometry.replan", "span", "search.grid",
            "Mid-search geometry re-plan of a halving rung's "
            "surviving candidates (lane reclamation; carries iter and "
            "whether replanning was on).",
            layer="planning", mirror=True),
    SpanDef("doctor.analyze", "span", "search.grid",
            "Post-fit critical-path attribution: decomposing the "
            "search wall into lanes (compile, stage, compute, gather, "
            "queue wait, faults, padding, narrowing).",
            layer="search API", mirror=True),
    SpanDef("doctor.sentinel", "span", "search.grid",
            "Cross-run regression check of the attribution block "
            "against the persistent run-log baseline.",
            layer="search API", mirror=True),
    SpanDef("fit.prepare", "span", "search.grid",
            "Host work before anything is planned: parameter routing, "
            "check_cv and the splits (in fit), then dtype resolution, "
            "densify, the family's prepare_data and the fold masks (in "
            "the compiled tier).",
            layer="search API", mirror=True),
    SpanDef("fit.plan", "span", "search.grid",
            "Planning one evaluate_candidates call: compile groups "
            "(build_compile_groups is its child), the mesh and the "
            "memory ledger's baseline in the compiled tier, then "
            "convergence sorting, launch geometry and the ledger's "
            "width caps and footprints in _run_groups.",
            layer="planning", mirror=True),
    SpanDef("fit.report", "span", "search.grid",
            "After the last launch: joining the compile thread and "
            "rendering the pipeline, scheduler, chunkloop and prefix "
            "blocks of search_report, feeding the geometry cost "
            "model.",
            layer="search API", mirror=True),
    SpanDef("fit.results", "span", "search.grid",
            "_format_results: the accumulated score arrays to the "
            "cv_results_ dict (means, stds, ranks, masked params).",
            layer="search API", mirror=True),
    # search/stream.py
    SpanDef("stream.plan", "span", "search.stream",
            "Analytic shard-plan sizing for a streamed search "
            "(carries n_shards, shard_rows, row_bytes, capped).",
            layer="search API"),
    SpanDef("stream.fit_pass", "span", "search.stream",
            "The streamed FIT pass: every live shard uploaded and "
            "folded into the per-group fit-statistic accumulators.",
            layer="search API"),
    SpanDef("stream.finalize", "span", "search.stream",
            "Per-chunk candidate finalize: vmapped solves over the "
            "folded statistics (one cheap launch per live chunk).",
            layer="search API"),
    SpanDef("stream.score_pass", "span", "search.stream",
            "The streamed SCORE pass: shards re-streamed through "
            "predict into the default scorer's sufficient statistics.",
            layer="search API"),
    # search/halving.py
    SpanDef("halving.rung", "span", "search.halving",
            "One successive-halving rung: fit + score of the "
            "surviving candidates at this rung's resource (carries "
            "iter, n_candidates, n_resources).",
            layer="search API"),
    SpanDef("chunkloop.segment", "span", "search.grid",
            "Host-side staging of one scan segment (chunk_loop="
            "\"scan\"): the member chunks' operands stacked along the "
            "leading step axis and uploaded as one slab (carries "
            "group, n_chunks).",
            layer="search API", mirror=True),
    SpanDef("chunkloop.scan", "span", "search.grid",
            "One lax.scan launch executing a whole scan segment — "
            "n_chunks member chunks — as a single device program "
            "(carries group, n_chunks, and topk: the on-device rung "
            "elimination's keep count, 0 when the carry is score-"
            "only).",
            layer="search API"),
    SpanDef("prefix.stage", "span", "search.grid",
            "The shared-prefix stage-1 loop: every DISTINCT Pipeline "
            "prefix digest computed/restored once, vectorized over "
            "folds, before suffix chunks launch (carries "
            "n_distinct).",
            layer="search API", mirror=True),
    # parallel/taskgrid.py
    SpanDef("build_compile_groups", "span", "parallel.taskgrid",
            "Partitioning candidates into static-signature groups.",
            layer="planning"),
    SpanDef("pad_chunk", "span", "parallel.taskgrid",
            "Slicing + padding one chunk to its launch width.",
            layer="planning", mirror=True),
    # parallel/mesh.py
    SpanDef("build_mesh", "span", "parallel.mesh",
            "Mesh construction over the visible devices.",
            layer="planning", mirror=True),
    SpanDef("device_put.replicate", "span", "parallel.mesh",
            "Replicated device_put (the TPU-native sc.broadcast).",
            layer="data plane", mirror=True),
    SpanDef("device_put.shard", "span", "parallel.mesh",
            "Leading-axis sharded device_put.",
            layer="data plane", mirror=True),
    SpanDef("device_put.broadcast", "span", "search.grid",
            "The search's whole X/y + fold-mask broadcast phase "
            "(plane-cached uploads; recorded retroactively).",
            layer="data plane", mirror=True),
    SpanDef("device_get", "span", "parallel.mesh",
            "Blocking device->host transfer.",
            layer="data plane", mirror=True),
    SpanDef("device_get.allgather", "span", "parallel.mesh",
            "Multi-controller device_get via process_allgather.",
            layer="data plane", mirror=True),
    # parallel/dataplane.py
    SpanDef("dataplane.upload", "span", "parallel.dataplane",
            "One host->device transfer (carries `bytes`).",
            layer="data plane", mirror=True),
    SpanDef("dataplane.tile", "span", "parallel.dataplane",
            "On-device fold-mask tiling (no host transfer).",
            layer="data plane", mirror=True),
    SpanDef("dataplane.derive", "span", "parallel.dataplane",
            "One derived-buffer materialization (a cache miss in "
            "DataPlane.derived — e.g. a shared-prefix transformed "
            "design matrix; carries `bytes`, `label`).",
            layer="data plane", mirror=True),
    SpanDef("dataplane.fingerprint", "span", "parallel.dataplane",
            "Content digest (blake2b) of one host array, the data "
            "plane's cache key (carries `bytes`).",
            layer="data plane", mirror=True),
    # parallel/programstore.py
    SpanDef("programstore.load", "span", "parallel.programstore",
            "One AOT-artifact store lookup (carries `bytes`, `hit` and "
            "the serving `source`: memory/disk/miss).",
            layer="program build"),
    SpanDef("programstore.save", "span", "parallel.programstore",
            "Serialize + atomic publish of one AOT artifact (carries "
            "`bytes`).",
            layer="program build"),
    SpanDef("programstore.prewarm", "span", "parallel.programstore",
            "Manifest-driven artifact preload at session init.",
            layer="program build"),
    # parallel/pipeline.py
    SpanDef("stage", "span", "parallel.pipeline",
            "Chunk staging (host prep + device_put) on sst-stage.",
            layer="chunk pipeline", mirror=True),
    SpanDef("dispatch", "span", "parallel.pipeline",
            "Async launch enqueue (first dispatch includes compile).",
            layer="chunk pipeline", mirror=True),
    SpanDef("compute.wait", "span", "parallel.pipeline",
            "Blocking wait for a launch's outputs on sst-gather.",
            layer="chunk pipeline", mirror=True),
    SpanDef("compute", "span", "parallel.pipeline",
            "Device-occupancy estimate on the virtual `device` track.",
            layer="chunk pipeline"),
    SpanDef("gather", "span", "parallel.pipeline",
            "Blocking device->host result transfer.",
            layer="chunk pipeline", mirror=True),
    SpanDef("finalize", "span", "parallel.pipeline",
            "Result writes / checkpoint append, dispatch order.",
            layer="chunk pipeline", mirror=True),
    SpanDef("compile", "span", "parallel.pipeline",
            "AOT lower+compile on the sst-compile thread (carries "
            "label and, from the process ledger, what the build was: "
            "cache hit/miss/off, cache_load_s, trace_s, lower_s).",
            layer="program build", mirror=True),
    # obs/process.py
    SpanDef("compile.wait", "span", "obs.process",
            "A dispatching thread standing for a build still in flight "
            "on sst-compile: a group's first fused dispatch "
            "(where=dispatch, inside `dispatch`), a rung barrier "
            "(where=drain) or the join after the last launch "
            "(where=close, inside `fit.report`).  Its seconds reach "
            "search_report[\"process\"][\"build_blocked_s\"] whatever "
            "the tracer's state.",
            layer="program build", mirror=True),
    # parallel/faults.py
    SpanDef("launch.retry", "span", "parallel.faults",
            "Transient-fault retry of a launch's phases.",
            layer="supervisor", mirror=True),
    SpanDef("launch.bisect", "span", "parallel.faults",
            "OOM recovery: chunk bisected into half-width launches.",
            layer="supervisor", mirror=True),
    SpanDef("launch.host_fallback", "span", "parallel.faults",
            "OOM recovery bottomed out into per-candidate host runs.",
            layer="supervisor", mirror=True),
    SpanDef("launch.isolate", "span", "parallel.faults",
            "FATAL recovery: chunk re-run through the quarantine "
            "bisect hook to isolate the poison candidate.",
            layer="supervisor", mirror=True),
    # serve/executor.py
    SpanDef("serve.submit", "span", "serve.executor",
            "Admission + enqueue of one submitted search.",
            layer="executor", mirror=True),
    SpanDef("sched.queue.wait", "span", "serve.executor",
            "A search's dispatch blocked while its chunk waits in the "
            "multi-tenant fair-share queue.",
            layer="executor", mirror=True),
    SpanDef("sched.dispatch", "span", "serve.executor",
            "One routed chunk launch enqueued on the shared "
            "sst-dispatch loop (carries tenant, handle, cost).",
            layer="executor", mirror=True),
    SpanDef("sched.fuse", "span", "serve.executor",
            "One fused launch: same-key chunks from several searches "
            "coalesced into a single wide device program (carries "
            "n_members, lanes, cost).",
            layer="executor", mirror=True),
    # serve/journal.py
    SpanDef("journal.append", "span", "serve.journal",
            "One durable service-journal append (checksummed WAL "
            "record, flushed + fsynced before the submit/transition "
            "proceeds; carries kind).",
            layer="journal", mirror=True),
    # obs/telemetry.py
    SpanDef("telemetry.sample", "span", "obs.telemetry",
            "One fleet-telemetry sampler tick (provider polls).",
            layer="observability"),
    # parallel/memledger.py
    SpanDef("memory.sample", "span", "parallel.memledger",
            "One device-memory reconciliation tick: jax memory_stats "
            "across the local devices (carries bytes_in_use and "
            "whether the backend measures at all).",
            layer="planning", mirror=True),
    SpanDef("memory.footprint", "instant", "parallel.memledger",
            "One compile group's modeled device footprint registered "
            "with the ledger (carries group, width, chunk_bytes, "
            "modeled_bytes and whether the HBM ceiling capped the "
            "width) — trace_summary digests these into the per-group "
            "memory line.",
            layer="planning"),
    # obs/heartbeat.py
    SpanDef("heartbeat.beat", "instant", "obs.heartbeat",
            "One in-flight device beat from the scanned program's "
            "step body (jax.debug.callback; carries key, group, "
            "step) — only recorded when the heartbeat beacon is on "
            "(TpuConfig.heartbeat / SST_HEARTBEAT).",
            layer="observability"),
    # utils/session.py
    SpanDef("session.init", "span", "utils.session",
            "TpuSession bootstrap (mesh, caches, fault plan).",
            layer="session", mirror=True),
    SpanDef("session.recover", "span", "utils.session",
            "Warm-restart scan: the service journal's non-terminal "
            "entries folded into a RecoveryReport.",
            layer="session", mirror=True),
    # obs/log.py
    SpanDef("log", "instant", "obs.log",
            "A stdout-parity verbose line mirrored onto the timeline.",
            layer="observability"),
    # jax.named_scope phases inside compiled programs (device side)
    SpanDef("sst.fit", "scope", "search.grid",
            "The family's fit inside a launch program (fit_batch_tb, "
            "fused_batch, the nested fit).",
            layer="solvers"),
    SpanDef("sst.score", "scope", "search.grid",
            "The scoring epilogue inside a launch program: model "
            "views, metric cores, the NaN-health check.",
            layer="solvers"),
    SpanDef("glm_lbfgs.init", "scope", "ops.solvers",
            "glm_lbfgs_batched before its loop: Z0 = Ax(x0), f0, g0.",
            layer="solvers"),
    SpanDef("glm_lbfgs.direction", "scope", "ops.solvers",
            "The two-loop recursion and the direction's guards.",
            layer="solvers"),
    SpanDef("glm_lbfgs.forward", "scope", "ops.solvers",
            "Zp = Ax(p), the one forward matmul of an iteration.",
            layer="solvers"),
    SpanDef("glm_lbfgs.linesearch", "scope", "ops.solvers",
            "The trial losses of an iteration (the caller's one-pass "
            "evaluator over (Z, Zp) where it hands one: the first four "
            "steps, and the other ls_trials - 4 under a conditional; "
            "else a vmap of the loss over the trial axis), Armijo, "
            "each lane's pick.",
            layer="solvers"),
    SpanDef("glm_lbfgs.step", "scope", "ops.solvers",
            "Step masking, x_new, Z_new, f_new.",
            layer="solvers"),
    SpanDef("glm_lbfgs.gradient", "scope", "ops.solvers",
            "data_grad(Z) and reg_grad(x): the elementwise half of "
            "the gradient.",
            layer="solvers"),
    SpanDef("glm_lbfgs.backward", "scope", "ops.solvers",
            "AT(dL/dZ), the one backward matmul of an iteration.",
            layer="solvers"),
    SpanDef("glm_lbfgs.history", "scope", "ops.solvers",
            "s, y, the memory update, the stall detector and done.",
            layer="solvers"),
    SpanDef("sst.box_fista.gradient", "scope", "models.svm",
            "_box_fista: the caller's gradient at the momentum point, "
            "for the kernel duals one (subproblems, n) @ (n, n) product, "
            "or in the block-compact layout the product by class and "
            "the selection of each dual's two blocks.",
            layer="solvers"),
    SpanDef("sst.box_fista.project", "scope", "models.svm",
            "_box_fista: the gradient step and the caller's projection "
            "(for SVC the bisection onto box and hyperplane).",
            layer="solvers"),
    SpanDef("sst.box_fista.momentum", "scope", "models.svm",
            "_box_fista: Nesterov's extrapolation, the per-lane "
            "prox-gradient residual and done.",
            layer="solvers"),
    SpanDef("sst.svc.gram", "scope", "models.svm",
            "SVCFamily: one candidate's (n, n) kernel matrix.",
            layer="solvers"),
    SpanDef("sst.svc.power_step", "scope", "models.svm",
            "SVCFamily: the power iterations for 1/lambda_max, the "
            "dual's step.",
            layer="solvers"),
    SpanDef("sst.svc.intercept", "scope", "models.svm",
            "SVCFamily: each pair's intercept from the KKT conditions.",
            layer="solvers"),
    SpanDef("sst.svc.decision", "scope", "models.svm",
            "SVCFamily: every pair's decision value on all rows, the "
            "cache the scoring epilogue votes on.",
            layer="solvers"),
    SpanDef("sst.svc.compact", "scope", "models.svm",
            "SVCFamily, block-compact duals: the rows into class order "
            "once a launch, each candidate's boxes in the compact "
            "layout, and its decisions back into the caller's row order.",
            layer="solvers"),
    SpanDef("sst.mlp.gather", "scope", "models.mlp",
            "A minibatch step: the batch's rows and targets gathered "
            "from the fold's training rows in the epoch's order.",
            layer="solvers"),
    SpanDef("sst.mlp.forward", "scope", "models.mlp",
            "A minibatch step: every layer's activations, the batch's "
            "loss and its regulariser.",
            layer="solvers"),
    SpanDef("sst.mlp.backward", "scope", "models.mlp",
            "A minibatch step: sklearn's _backprop, the deltas and "
            "every layer's gradients.",
            layer="solvers"),
    SpanDef("sst.mlp.update", "scope", "models.mlp",
            "A minibatch step: the optimiser (sklearn's AdamOptimizer "
            "or momentum SGD) on weights and moments.",
            layer="solvers"),
    SpanDef("sst.mlp.epoch", "scope", "models.mlp",
            "Once an epoch: the order of the training rows, the "
            "epoch's loss or validation score, and sklearn's stopping "
            "rules.",
            layer="solvers"),
    SpanDef("sst.tree.bootstrap", "scope", "models.trees",
            "Once a forest's tree: the rows' weights, the fold's mask "
            "times the Poisson(1) bootstrap counts.",
            layer="solvers"),
    SpanDef("sst.tree.partition", "scope", "ops.tree_hist",
            "A tree level on a TPU: the counted rows sorted into node "
            "order, the items (tile, node, first and last row) of the "
            "level, and the sorted copy of codes and statistics.",
            layer="solvers"),
    SpanDef("sst.tree.histogram", "scope", "ops.tree_hist",
            "A tree level's (node, feature, bin) histograms: the "
            "grouped one-hot product kernel on a TPU, segment sums "
            "elsewhere.",
            layer="solvers"),
    SpanDef("sst.tree.split", "scope", "ops.trees",
            "A tree level: cumulative sums over bins, the gains, the "
            "nodes' feature subsets, each node's best (feature, bin) "
            "and its children's sums; at the tree's end the leaf "
            "values.",
            layer="solvers"),
    SpanDef("sst.tree.route", "scope", "ops.trees",
            "A tree level: every row that is not in a leaf to the left "
            "or right child of its node.",
            layer="solvers"),
    SpanDef("sst.tree.predict", "scope", "models.trees",
            "Once a tree: its leaf values at the node each row ended "
            "in, added to the votes (forests) or the staged "
            "predictions (boosting).",
            layer="solvers"),
    SpanDef("sst.boost.gradient", "scope", "models.trees",
            "A boosting stage, before its trees: the loss's mean of the "
            "raw scores F (sigmoid, softmax), each row's gradient and "
            "hessian, and the stage's row weights (the fold's mask "
            "times the subsample draw).",
            layer="solvers"),
    SpanDef("sst.boost.update", "scope", "models.trees",
            "A boosting stage, after its trees: F += learning_rate x "
            "the trees' leaf values, on the lanes whose own "
            "n_estimators the stage is under.",
            layer="solvers"),
    SpanDef("sst.prefix.transform", "scope", "models.pipeline",
            "PipelineFamily.prefix_transform: the transformer chain "
            "fitted on each fold's training rows and applied to all "
            "rows, the (folds, n, d) buffer the shared-prefix stage "
            "caches (prefix.stage is its host span).",
            layer="search API"),
    # async virtual tracks (name prefixes)
    SpanDef("launch", "async", "parallel.pipeline",
            "Whole-launch span (dispatch..finalize) per chunk, on the "
            "`launches` track.",
            layer="chunk pipeline"),
    SpanDef("compile-group", "async", "parallel.pipeline",
            "Compile-group boundary span on the `compile-groups` "
            "track.",
            layer="chunk pipeline"),
    SpanDef("heartbeat.segment", "async", "obs.heartbeat",
            "One scan segment's register..complete lifetime on the "
            "`progress` track (carries group, steps, beats) — the "
            "per-segment progress lane the Chrome export lays the "
            "heartbeat.beat instants over.",
            layer="observability"),
)

#: async-span name prefixes, longest first so `compile-group 3` never
#: matches a shorter prefix by accident.
ASYNC_PREFIXES: Tuple[str, ...] = tuple(sorted(
    (d.name for d in SPAN_VOCABULARY if d.kind == "async"),
    key=len, reverse=True))

#: virtual track names the exporter lays spans out on.
KNOWN_TRACKS: Tuple[str, ...] = ("device", "launches", "compile-groups",
                                 "progress")


#: spans also written into a live ``jax.profiler`` trace, as
#: ``sst.<name>`` (``obs/trace.py`` reads this set at every site)
MIRRORED_SPANS: frozenset = frozenset(
    d.name for d in SPAN_VOCABULARY if d.mirror)


def known_span_names() -> frozenset:
    """Exact names the tracer records (spans and instants)."""
    return frozenset(d.name for d in SPAN_VOCABULARY
                     if d.kind in ("span", "instant"))


def known_scope_names() -> frozenset:
    """Registered ``jax.named_scope`` names."""
    return frozenset(d.name for d in SPAN_VOCABULARY if d.kind == "scope")


def async_prefix(name: str) -> Optional[str]:
    """The registered async prefix `name` falls under, or None."""
    for p in ASYNC_PREFIXES:
        if name == p or name.startswith(p + " "):
            return p
    return None


def is_known_span(name: str) -> bool:
    """Is `name` (exact span/instant, or a registered async prefix
    form) part of the vocabulary?"""
    return name in known_span_names() or async_prefix(name) is not None


def vocabulary_markdown() -> str:
    """The span-vocabulary table ``dev/build_api_docs.py`` renders into
    ``docs/API.md`` — defined here, next to the vocabulary, so
    sstlint's ``docs-stale`` rule can compare the docs against it
    without importing the (jax-heavy) rest of the package."""
    out = [
        "## Span vocabulary\n",
        "\nEvery trace name the engine records, pinned in "
        "`spark_sklearn_tpu/obs/spans.py` (async entries are name "
        "PREFIXES on virtual tracks).\n",
        "\n`mirrored` spans are also written into a live `jax.profiler` "
        "trace as the host event `sst.<name>`; a `scope` is a "
        "`jax.named_scope` that names device operations there.\n",
        "\n| name | kind | layer | mirrored | module | description |\n"
        "|---|---|---|---|---|---|\n",
    ]
    for d in SPAN_VOCABULARY:
        out.append(f"| `{d.name}` | {d.kind} | {d.layer} | "
                   f"{'yes' if d.mirror else ''} | {d.module} | "
                   f"{d.description} |\n")
    return "".join(out)
