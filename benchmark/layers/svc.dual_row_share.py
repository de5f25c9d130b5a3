"""Columns one dual's iterate holds in the traced search's launches
(``search_report["dual_rows_per_launch"]``: two class blocks in the
block-compact layout, every row in the dense one), as a share of the
configuration's rows.  ``None`` where the report has no such counter (a
program whose duals have one layout only)."""


def read(ctx):
    rows = ctx["report"].get("dual_rows_per_launch")
    if not rows:
        return None
    return 100.0 * max(rows) / ctx["config"]["data"]["n_samples"]
