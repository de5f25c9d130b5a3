"""The least time the chip needs for the level histograms' bytes and
additions (``work_forest.py``: one read of each in-bag training row's code
bytes and statistics a tree-level, ``(1 + classes) x features`` additions a
row; peaks from ``peaks.json``) over the device seconds under
``sst.tree.histogram`` in the traced search.  Which of the two bounds it is
printed on an earlier line."""


def read(ctx):
    scopes = ctx["load_named"]("scopes:read")(ctx)
    if scopes is None:
        return None
    needs = ctx["load_named"](ctx["config"]["work"])(
        ctx["config"], ctx["n_candidates"], ctx["report"])
    device_s = ctx["load_named"]("layers/forest.device_s:seconds")(
        scopes, ("sst.tree.histogram",))
    if needs is None or device_s <= 0.0:
        return None
    work = ctx["work"]
    least, bound = work.roofline_seconds(
        needs["fit_flops"] / ctx["chips"], needs["fit_bytes"] / ctx["chips"],
        work.load_peaks(ctx["device"]["kind"]))
    print(f"tree_histogram_roofline: least {least:.4f} s bound by "
          f"{bound}, device time under sst.tree.histogram {device_s:.4f} s",
          flush=True)
    return 100.0 * least / device_s if least > 0.0 else None
