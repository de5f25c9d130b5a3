"""The least time the chip needs for the fit launches' bytes and FLOPs
(from shapes, ``work.py``; peaks from ``peaks.json``) over the device time
of the launches in the trace.  ``glm_lbfgs_batched`` has no named scope
yet, so its device time is the time in which any device operation of the
traced search ran: the fit, the scoring epilogue fused into its launch,
and the transfers.
Which of the two bounds it is printed on an earlier line."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    work = ctx["work"]
    needs = ctx["load_named"](ctx["config"]["work"])(
        ctx["config"], ctx["n_candidates"], ctx["report"])
    if needs is None:
        return None
    peaks = work.load_peaks(ctx["device"]["kind"])
    # a launch over several chips splits its lanes: each chip has a share
    least, bound = work.roofline_seconds(
        needs["fit_flops"] / ctx["chips"], needs["fit_bytes"] / ctx["chips"],
        peaks)
    device_s = trace["busy_s"]
    print(f"glm_lbfgs_batched_roofline: least {least:.4f} s bound by "
          f"{bound}, device time {device_s:.4f} s", flush=True)
    return 100.0 * least / device_s if device_s > 0 else None
