"""Span-vocabulary and report-schema drift checkers.

The observability surfaces are contracts: ``tools/trace_summary.py``
digests span names, and users script against ``search_report`` keys.
These rules pin both to their single sources of truth —
``spark_sklearn_tpu/obs/spans.py`` (the span vocabulary) and
``spark_sklearn_tpu/obs/metrics.py`` (the ``*_BLOCK_SCHEMA``
constants) — and keep ``docs/API.md`` fresh against them.
"""

from __future__ import annotations

import ast
from typing import Iterable, Optional, Set

from tools.sstlint import astutil
from tools.sstlint.core import Context, Finding, ModuleInfo, rule

#: tracer-recording call attribute names and which argument carries
#: the span name
_RECORDERS = {"span": 0, "instant": 0, "record_span": 0,
              "record_async": 0}


def _span_calls(mod: ModuleInfo):
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        if not isinstance(node.func, ast.Attribute):
            continue
        if node.func.attr not in _RECORDERS:
            continue
        # only tracer-ish receivers: get_tracer(), tracer, tr,
        # self._tracer — anything whose chain mentions trace(r)
        recv = node.func.value
        chain = (astutil.attr_chain(recv) or "").lower()
        if isinstance(recv, ast.Call):
            chain = (astutil.call_name(recv) or "").lower()
        if "trace" not in chain and chain not in ("tr",):
            continue
        yield node


def _scope_calls(mod: ModuleInfo):
    """``jax.named_scope(...)`` / ``named_scope(...)`` calls."""
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call) and (
                astutil.call_name(node) or "").split(".")[-1] \
                == "named_scope":
            yield node


def _span_name(node: ast.Call) -> Optional[str]:
    """The literal (or f-string constant prefix) name of a recorder
    call; None when the name is not statically known."""
    if not node.args:
        return None
    arg = node.args[0]
    s = astutil.literal_str(arg)
    if s is not None:
        return s
    if isinstance(arg, ast.JoinedStr) and arg.values:
        first = arg.values[0]
        prefix = astutil.literal_str(first)
        if prefix is not None:
            return prefix.rstrip()
    return None


def _load_spans(ctx: Context):
    path = ctx.project.spans_path
    if not path or not path.is_file():
        return None
    return astutil.load_module_by_path(path, "_sstlint_spans")


@rule("span-unknown-name")
def check_span_vocabulary(ctx: Context) -> Iterable[Finding]:
    """Every recorded span/instant/async name must be registered in
    the span vocabulary (``obs/spans.py``) — trace_summary groups and
    documents by those names, so an ad-hoc name silently falls out of
    every digest.  Likewise every ``jax.named_scope`` name (kind
    "scope"): the benchmark's trace readers sum device time by them."""
    spans = _load_spans(ctx)
    if spans is None:
        return
    scope_names = getattr(spans, "known_scope_names", frozenset)()
    for mod in ctx.modules:
        for node in _scope_calls(mod):
            name = _span_name(node)
            if name in scope_names or \
                    mod.suppressed("span-unknown-name", node.lineno):
                continue
            yield Finding(
                "span-unknown-name", mod.relpath, node.lineno,
                f"named scope {name!r} is not registered (kind "
                "\"scope\") in obs/spans.py SPAN_VOCABULARY"
                if name is not None else
                "named scope is not a literal — sstlint cannot check "
                "it against the vocabulary",
                symbol=name or f"<dynamic>@{mod.qualname(node)}")
        if mod.relpath.endswith("obs/trace.py"):
            continue               # the recorder itself
        for node in _span_calls(mod):
            name = _span_name(node)
            if name is None:
                if mod.suppressed("span-unknown-name", node.lineno):
                    continue
                yield Finding(
                    "span-unknown-name", mod.relpath, node.lineno,
                    "span name is not a literal/f-string with a "
                    "registered constant prefix — sstlint cannot "
                    "check it against the vocabulary",
                    symbol=f"<dynamic>@{mod.qualname(node)}")
                continue
            ok = spans.is_known_span(name) or (
                node.func.attr == "record_async"
                and spans.async_prefix(name) is not None)
            if not ok:
                if mod.suppressed("span-unknown-name", node.lineno):
                    continue
                yield Finding(
                    "span-unknown-name", mod.relpath, node.lineno,
                    f"span name {name!r} is not registered in "
                    "obs/spans.py SPAN_VOCABULARY",
                    symbol=name)


@rule("span-not-context-managed")
def check_span_with(ctx: Context) -> Iterable[Finding]:
    """``tracer.span(...)`` must be opened via ``with`` — a manually
    entered span with no guaranteed ``__exit__`` leaks an unclosed
    event on any exception path and corrupts the nesting the exporter
    relies on.  (``record_span``/``record_async`` take explicit
    timestamps and are exempt.)"""
    for mod in ctx.modules:
        if mod.relpath.endswith("obs/trace.py"):
            continue
        for node in _span_calls(mod):
            if node.func.attr != "span":
                continue
            parent = mod.parents.get(node)
            if isinstance(parent, ast.withitem):
                continue
            # allow `with x.span(...) as s` via withitem, and direct
            # return of a span from helper wrappers is disallowed
            if mod.suppressed("span-not-context-managed", node.lineno):
                continue
            yield Finding(
                "span-not-context-managed", mod.relpath, node.lineno,
                "tracer.span(...) used outside a `with` statement — "
                "open spans via context manager so __exit__ always "
                "runs",
                symbol=f"{mod.qualname(node) or '<module>'}"
                       f":{_span_name(node) or '?'}")


def _schema_keys(metrics_mod, attr: str) -> Optional[Set[str]]:
    defs = getattr(metrics_mod, attr, None)
    if defs is None:
        return None
    return {d.name for d in defs}


@rule("schema-block-drift")
def check_schema_drift(ctx: Context) -> Iterable[Finding]:
    """Every key a producer renders into a pinned ``search_report``
    block must be declared in its ``*_BLOCK_SCHEMA`` — and every
    declared key must be produced somewhere — so the documented report
    schema can never drift from what fit() actually returns."""
    if not ctx.project.metrics_path or \
            not ctx.project.metrics_path.is_file():
        return
    metrics = astutil.load_module_by_path(
        ctx.project.metrics_path, "_sstlint_metrics")
    for spec in ctx.project.blocks:
        declared = _schema_keys(metrics, spec.schema_attr)
        if declared is None:
            yield Finding(
                "schema-block-drift",
                _rel(ctx, ctx.project.metrics_path), 1,
                f"schema constant {spec.schema_attr} not found in the "
                "metrics module",
                symbol=spec.schema_attr)
            continue
        produced: Set[str] = set()
        anchor_line = 1
        anchor_rel = _rel(ctx, ctx.project.metrics_path)
        for prod in spec.producers:
            mod = ctx.module(prod.relpath)
            if mod is None:
                continue
            anchor_rel = mod.relpath
            if prod.kind == "dict-keys":
                produced |= astutil.dict_literal_keys_in(mod, prod.target)
            elif prod.kind == "subscript-var":
                produced |= astutil.subscript_store_keys(mod, prod.target)
        for extra in sorted(produced - declared):
            yield Finding(
                "schema-block-drift", anchor_rel, anchor_line,
                f"search_report[{spec.block!r}] renders key {extra!r} "
                f"that is not declared in {spec.schema_attr}",
                symbol=f"{spec.block}:+{extra}")
        for missing in sorted(declared - produced):
            yield Finding(
                "schema-block-drift",
                _rel(ctx, ctx.project.metrics_path), 1,
                f"{spec.schema_attr} declares {missing!r} but no "
                f"producer of search_report[{spec.block!r}] writes it",
                symbol=f"{spec.block}:-{missing}")


#: registry-handle methods and the receivers we treat as registries
_REG_METHODS = frozenset({"counter", "gauge", "label", "histogram",
                          "series", "struct", "put"})
_REG_RECEIVERS = frozenset({"metrics", "reg", "registry"})


@rule("report-key-undeclared")
def check_report_keys(ctx: Context) -> Iterable[Finding]:
    """Every metric name the engine writes through the strict registry
    (``metrics.counter("...")`` etc.) must be declared in
    ``SEARCH_REPORT_SCHEMA``, and every declared top-level key must be
    written somewhere — the full ``search_report`` surface stays
    pinned in one table.  A row that names a launch ``stat`` is written
    by the one loop over those rows (``search/launch.py::record_stats``),
    not by a literal: it counts as written where some module outside
    the schema's own spells the stat's name, i.e. a family hook reports
    it."""
    if not ctx.project.metrics_path or \
            not ctx.project.metrics_path.is_file():
        return
    metrics = astutil.load_module_by_path(
        ctx.project.metrics_path, "_sstlint_metrics")
    declared = _schema_keys(metrics, "SEARCH_REPORT_SCHEMA")
    if declared is None:
        return
    used: Set[str] = set()
    first_use = {}
    by_stat = {d.stat: d.name
               for d in metrics.SEARCH_REPORT_SCHEMA
               if getattr(d, "stat", None)}
    metrics_rel = _rel(ctx, ctx.project.metrics_path)
    for mod in ctx.modules:
        reports_stats = bool(by_stat) and mod.relpath != metrics_rel
        for node in ast.walk(mod.tree):
            if reports_stats and isinstance(node, ast.Constant) \
                    and node.value in by_stat:
                used.add(by_stat[node.value])
            if not isinstance(node, ast.Call) or not node.args:
                continue
            if not isinstance(node.func, ast.Attribute):
                continue
            if node.func.attr not in _REG_METHODS:
                continue
            recv = astutil.attr_chain(node.func.value) or ""
            if recv.split(".")[-1] not in _REG_RECEIVERS:
                continue
            name = astutil.literal_str(node.args[0])
            if name is None:
                continue
            used.add(name)
            first_use.setdefault(name, (mod.relpath, node.lineno))
    for extra in sorted(used - declared):
        rel, line = first_use[extra]
        mod = ctx.module(rel)
        if mod is not None and mod.suppressed(
                "report-key-undeclared", line):
            continue
        yield Finding(
            "report-key-undeclared", rel, line,
            f"registry metric {extra!r} is not declared in "
            "SEARCH_REPORT_SCHEMA",
            symbol=f"+{extra}")
    for missing in sorted(declared - used):
        yield Finding(
            "report-key-undeclared", _rel(ctx, ctx.project.metrics_path),
            1,
            f"SEARCH_REPORT_SCHEMA declares {missing!r} but nothing "
            "writes it through a registry handle",
            symbol=f"-{missing}")


@rule("docs-stale")
def check_docs_fresh(ctx: Context) -> Iterable[Finding]:
    """``docs/API.md`` must contain the exact generated sections that
    ``dev/build_api_docs.py`` renders today — the ``search_report``
    schema (``obs.metrics.schema_markdown()``), the span vocabulary
    (``obs.spans.vocabulary_markdown()``), and the sstlint rule catalog
    (``tools.sstlint.catalog_markdown()``) — so regenerating the docs
    is part of changing any of them."""
    if not ctx.project.metrics_path or \
            not ctx.project.metrics_path.is_file():
        return
    if not ctx.project.docs_api or not ctx.project.docs_api.is_file():
        yield Finding(
            "docs-stale", "docs/API.md", 1,
            "docs/API.md is missing; run `python dev/build_api_docs.py`",
            symbol="missing")
        return
    docs_text = ctx.project.docs_api.read_text()
    metrics = astutil.load_module_by_path(
        ctx.project.metrics_path, "_sstlint_metrics")
    sections = [("obs.metrics.schema_markdown()", "schema-section",
                 getattr(metrics, "schema_markdown", lambda: "")())]
    spans = _load_spans(ctx)
    if spans is not None:
        sections.append(
            ("obs.spans.vocabulary_markdown()", "spans-section",
             getattr(spans, "vocabulary_markdown", lambda: "")()))
    from tools.sstlint import catalog_markdown
    sections.append(("tools.sstlint.catalog_markdown()",
                     "catalog-section", catalog_markdown()))
    for oracle, symbol, rendered in sections:
        if rendered and rendered not in docs_text:
            yield Finding(
                "docs-stale", _rel(ctx, ctx.project.docs_api), 1,
                f"docs/API.md no longer matches {oracle}; run "
                "`python dev/build_api_docs.py`",
                symbol=symbol)


def _rel(ctx: Context, path) -> str:
    try:
        return str(path.resolve().relative_to(ctx.project.root)
                   ).replace("\\", "/")
    except ValueError:
        return str(path)
