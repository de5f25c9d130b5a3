"""``sst.tree.histogram`` device seconds (the level histograms of every
feature of every node: the grouped one-hot product kernel on real-valued
statistics, three bfloat16 parts each) over the seconds of the whole stage
loop (``boost.device_s``) in the traced search."""


def read(ctx):
    return ctx["load_named"]("layers/boost.device_s:share")(
        ctx, "sst.tree.histogram")
