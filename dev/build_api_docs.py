"""Generate docs/API.md — the API reference build.

The reference ships a Sphinx tree published to gh-pages ([R docs/],
SURVEY §2.1); this box has no sphinx and no egress, so the build is a
small stdlib introspection pass over the public package surface:
module docstrings, public classes with signatures + method docs, public
functions.  `dev/run-tests.sh`-adjacent usage:

    python dev/build_api_docs.py        # rewrites docs/API.md

Runs on the CPU backend (jax.config): the docs build needs no
accelerator and must not hold one.
"""

import importlib
import inspect
import io
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

#: the documented surface: every module a reference user would touch
MODULES = [
    "spark_sklearn_tpu",
    "spark_sklearn_tpu.search.grid",
    "spark_sklearn_tpu.search.launch",
    "spark_sklearn_tpu.search.schedule",
    "spark_sklearn_tpu.search.scorers",
    "spark_sklearn_tpu.convert.converter",
    "spark_sklearn_tpu.keyed.keyed",
    "spark_sklearn_tpu.keyed.gapply",
    "spark_sklearn_tpu.sparse.csr",
    "spark_sklearn_tpu.parallel.mesh",
    "spark_sklearn_tpu.parallel.pipeline",
    "spark_sklearn_tpu.parallel.faults",
    "spark_sklearn_tpu.parallel.taskgrid",
    "spark_sklearn_tpu.parallel.dataplane",
    "spark_sklearn_tpu.parallel.memledger",
    "spark_sklearn_tpu.parallel.programstore",
    "spark_sklearn_tpu.serve",
    "spark_sklearn_tpu.serve.executor",
    "spark_sklearn_tpu.serve.journal",
    "spark_sklearn_tpu.models.base",
    "spark_sklearn_tpu.obs",
    "spark_sklearn_tpu.obs.trace",
    "spark_sklearn_tpu.obs.export",
    "spark_sklearn_tpu.obs.metrics",
    "spark_sklearn_tpu.obs.log",
    "spark_sklearn_tpu.obs.process",
    "spark_sklearn_tpu.obs.spans",
    "spark_sklearn_tpu.obs.telemetry",
    "spark_sklearn_tpu.obs.fleet",
    "spark_sklearn_tpu.obs.memory",
    "spark_sklearn_tpu.obs.attribution",
    "spark_sklearn_tpu.obs.runlog",
    "spark_sklearn_tpu.obs.provenance",
    "spark_sklearn_tpu.utils.locks",
    "spark_sklearn_tpu.utils.keycheck",
    "spark_sklearn_tpu.utils.journalspec",
    "spark_sklearn_tpu.utils.session",
    "spark_sklearn_tpu.utils.checkpoint",
    "spark_sklearn_tpu.utils.testing",
    "spark_sklearn_tpu.utils.native",
    "spark_sklearn_tpu.utils.multihost",
]


def _sig(obj):
    try:
        return str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"


def _doc(obj, indent=""):
    doc = inspect.getdoc(obj)
    if not doc:
        return ""
    return "\n".join(indent + line for line in doc.splitlines())


def _document_class(out, name, cls):
    out.write(f"### class `{name}{_sig(cls)}`\n\n")
    doc = _doc(cls)
    if doc:
        out.write(doc + "\n\n")
    for mname, meth in sorted(vars(cls).items()):
        if mname.startswith("_"):
            continue
        fn = meth.__func__ if isinstance(
            meth, (classmethod, staticmethod)) else meth
        if not callable(fn):
            continue
        out.write(f"- **`{mname}{_sig(fn)}`**")
        mdoc = inspect.getdoc(fn)
        if mdoc:
            first = mdoc.strip().splitlines()[0]
            out.write(f" — {first}")
        out.write("\n")
    out.write("\n")


def build_text():
    """The full docs/API.md content as a string (the freshness oracle
    sstlint's docs-stale rule and tests compare against)."""
    out = io.StringIO()
    out.write(
        "# API reference\n\n"
        "Generated by `python dev/build_api_docs.py` (stdlib "
        "introspection — the no-egress stand-in for the reference's "
        "Sphinx/gh-pages build).  Regenerate after changing public "
        "surfaces; `tests/test_doctests.py` keeps the examples "
        "honest.\n\n")
    for modname in MODULES:
        mod = importlib.import_module(modname)
        out.write(f"## `{modname}`\n\n")
        mdoc = inspect.getdoc(mod)
        if mdoc:
            out.write(mdoc.strip() + "\n\n")
        public = getattr(mod, "__all__", None) or [
            n for n in sorted(vars(mod))
            if not n.startswith("_")
            and getattr(vars(mod)[n], "__module__", None) == modname]
        for name in public:
            obj = getattr(mod, name, None)
            if obj is None or name == "__version__":
                continue
            if inspect.isclass(obj):
                _document_class(out, name, obj)
            elif callable(obj):
                out.write(f"### `{name}{_sig(obj)}`\n\n")
                doc = _doc(obj)
                if doc:
                    out.write(doc + "\n\n")
    # the search_report schema, rendered from the same MetricDef table
    # the engine writes through (obs/metrics.py) — cannot drift
    from spark_sklearn_tpu.obs.metrics import schema_markdown
    out.write(schema_markdown())
    out.write("\n")
    # both tables live next to their definitions (obs/spans.py and
    # tools/sstlint) so the docs-stale rule can compare docs/API.md
    # against them without a jax import
    from spark_sklearn_tpu.obs.spans import vocabulary_markdown
    out.write(vocabulary_markdown())
    out.write("\n")
    from spark_sklearn_tpu.utils.keycheck import (
        registry_markdown as keycheck_markdown)
    out.write(keycheck_markdown())
    out.write("\n")
    from spark_sklearn_tpu.utils.journalspec import (
        registry_markdown as journal_markdown)
    out.write(journal_markdown())
    out.write("\n")
    from tools.sstlint import catalog_markdown
    out.write(catalog_markdown())
    return out.getvalue()


def build(path):
    with open(path, "w") as f:
        f.write(build_text())
    return path


if __name__ == "__main__":
    target = build(os.path.join(REPO, "docs", "API.md"))
    print(f"wrote {target} "
          f"({os.path.getsize(target) / 1024:.0f} KiB)")
