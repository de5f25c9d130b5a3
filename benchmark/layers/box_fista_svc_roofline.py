"""The least time the chip needs for the dual iterations' bytes and FLOPs
(``work_svc.py``: each pair's own rows, one bfloat16 read of the kernel
matrix an iteration and distinct gamma; peaks from ``peaks.json``) over the
device seconds under ``sst.box_fista.*`` in the traced search.  Which of
the two bounds it is printed on an earlier line."""


def read(ctx):
    scopes = ctx["load_named"]("scopes:read")(ctx)
    if scopes is None:
        return None
    gammas = ctx["traffic"]["param_grid"].get("gamma")
    n_gammas = len(gammas) if isinstance(gammas, list) else 1
    needs = ctx["load_named"](ctx["config"]["work"])(
        ctx["config"], ctx["n_candidates"], ctx["report"], n_gammas)
    device_s = ctx["load_named"]("layers/svc.device_s:seconds")(
        scopes, ("sst.box_fista.",))
    if needs is None or needs["fit_bytes"] is None or device_s <= 0.0:
        return None
    work = ctx["work"]
    least, bound = work.roofline_seconds(
        needs["fit_flops"] / ctx["chips"], needs["fit_bytes"] / ctx["chips"],
        work.load_peaks(ctx["device"]["kind"]))
    print(f"box_fista_svc_roofline: least {least:.4f} s bound by {bound}, "
          f"device time under sst.box_fista.* {device_s:.4f} s", flush=True)
    return 100.0 * least / device_s if least > 0.0 else None
