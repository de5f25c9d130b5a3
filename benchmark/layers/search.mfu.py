"""FLOPs the window's searches REQUIRE over window time x chips x peak.
The configuration's model of work (``work.py``) counts them from each
search's report: real lanes and executed iterations only, plus scoring;
padding lanes and masked-out rows do not count.  Read from a chip only:
with no known peak for the device there is no number."""


def read(ctx):
    if ctx["device"]["platform"] != "tpu":
        return None
    model = ctx["load_named"](ctx["config"]["work"])
    flops = 0.0
    for rep in ctx["reports"]:
        needs = model(ctx["config"], ctx["n_candidates"], rep)
        if needs is None:
            return None
        flops += needs["flops"]
    peaks = ctx["work"].load_peaks(ctx["device"]["kind"])
    return 100.0 * flops / (ctx["window_s"] * ctx["chips"]
                            * peaks["flops_per_s"])
