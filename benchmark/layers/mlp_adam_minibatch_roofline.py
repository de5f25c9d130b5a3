"""The least time the chip needs for the minibatch steps' bytes and FLOPs
(``work_mlp.py``: Adam's 24 bytes a parameter a step a fit and one read of
each fold's minibatch, 6 FLOPs a weight a training row an epoch; peaks from
``peaks.json``) over the device seconds under ``sst.mlp.*`` in the traced
search.  Which of the two bounds it is printed on an earlier line."""


def read(ctx):
    scopes = ctx["load_named"]("scopes:read")(ctx)
    if scopes is None:
        return None
    needs = ctx["load_named"](ctx["config"]["work"])(
        ctx["config"], ctx["n_candidates"], ctx["report"])
    device_s = ctx["load_named"]("layers/mlp.device_s:seconds")(scopes)
    if needs is None or device_s <= 0.0:
        return None
    work = ctx["work"]
    least, bound = work.roofline_seconds(
        needs["fit_flops"] / ctx["chips"], needs["fit_bytes"] / ctx["chips"],
        work.load_peaks(ctx["device"]["kind"]))
    print(f"mlp_adam_minibatch_roofline: least {least:.4f} s bound by "
          f"{bound}, device time under sst.mlp.* {device_s:.4f} s",
          flush=True)
    return 100.0 * least / device_s if least > 0.0 else None
