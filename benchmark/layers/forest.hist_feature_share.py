"""Of the features a tree group's data has, the share a node's level
histograms hold: 100 x the sum of ``hist_features`` over the sum of
``n_features`` of the traced search's ``search_report["per_group"]``
records.  A forest whose ``max_features`` is a subset draws each node's own
features before the level's histograms and builds those and no others
(7 of 54 = 12.96 under "sqrt" at covtype's width); a program from before
that fact builds every feature of every node and masks the gains after, so
a forest's report without it reads 100.  ``None`` where the report is no
tree family's (no ``hist_features``, no ``hist_bytes_per_lane``)."""


def read(ctx):
    report = ctx["report"]
    groups = [rec for rec in (report.get("per_group") or {}).values()
              if rec.get("hist_features") and rec.get("n_features")]
    if groups:
        return (100.0 * sum(rec["hist_features"] for rec in groups)
                / sum(rec["n_features"] for rec in groups))
    return 100.0 if report.get("hist_bytes_per_lane") else None
