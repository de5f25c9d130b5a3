"""The least time the chip needs for the neighbour search the stated grid
requires (``work_knn.py``: each row's distances to its fold's training rows,
one bfloat16 pass of ``2 d`` operations each, the rows read and the lists
written once; peaks from ``peaks.json``) over the device seconds under
``sst.knn.distance`` and ``sst.knn.select`` in the traced search.  It counts
one pass of the product whatever implements it: three passes, distances to
a row's own fold and an unfused selection read as a lower share.  Which of
the two bounds it is printed on an earlier line."""


def read(ctx):
    found = ctx["load_named"]("layers/knn.device_s:search")(ctx)
    if found is None:
        return None
    device_s = ctx["load_named"]("layers/knn.device_s:seconds")(
        found[0], ("sst.knn.distance", "sst.knn.select"))
    needs = ctx["load_named"](ctx["config"]["work"])(
        ctx["config"], ctx["n_candidates"], ctx["report"])
    if needs is None or device_s <= 0.0:
        return None
    work = ctx["work"]
    least, bound = work.roofline_seconds(
        needs["fit_flops"] / ctx["chips"], needs["fit_bytes"] / ctx["chips"],
        work.load_peaks(ctx["device"]["kind"]))
    print(f"knn_neighbors_roofline: least {least:.4f} s bound by {bound}, "
          f"device time under sst.knn.distance + sst.knn.select "
          f"{device_s:.4f} s", flush=True)
    return 100.0 * least / device_s if least > 0.0 else None
