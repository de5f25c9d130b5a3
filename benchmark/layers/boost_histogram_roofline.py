"""The least time the chip needs for the level histograms' bytes and
additions of the search's DISTINCT stages (``work_boost.py``, counted from
the stated grid: one read of each training row's code bytes and two
statistics a tree-level, ``2 x features`` additions a row, the level's
histograms written once; peaks from ``peaks.json``) over the device seconds
under ``sst.tree.histogram`` in the traced search.  Which of the two bounds
it is printed on an earlier line."""


def read(ctx):
    found = ctx["load_named"]("layers/boost.device_s:stage_loop")(ctx)
    if found is None:
        return None
    device_s = ctx["load_named"]("layers/boost.device_s:seconds")(
        found[0], ("sst.tree.histogram",))
    needs = ctx["load_named"](ctx["config"]["work"])(
        ctx["config"], ctx["n_candidates"], ctx["report"])
    if needs is None or device_s <= 0.0:
        return None
    work = ctx["work"]
    least, bound = work.roofline_seconds(
        needs["fit_flops"] / ctx["chips"], needs["fit_bytes"] / ctx["chips"],
        work.load_peaks(ctx["device"]["kind"]))
    print(f"boost_histogram_roofline: least {least:.4f} s bound by "
          f"{bound}, device time under sst.tree.histogram {device_s:.4f} s",
          flush=True)
    return 100.0 * least / device_s if least > 0.0 else None
