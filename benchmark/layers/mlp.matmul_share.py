"""``sst.mlp.forward`` + ``sst.mlp.backward`` device seconds (the layers'
products and what the compiler fuses into them: on XLA:TPU all of the
optimiser, whose new weights and moments are the backward products' own
outputs) over the seconds under all ``sst.mlp.*`` scopes of the traced
search.  What is left is the gathers, the epoch's sorts and whatever of
the optimiser a compiler keeps apart."""


def read(ctx):
    scopes = ctx["load_named"]("scopes:read")(ctx)
    if scopes is None:
        return None
    seconds = ctx["load_named"]("layers/mlp.device_s:seconds")
    total = seconds(scopes)
    if total <= 0.0:
        return None
    return 100.0 * seconds(
        scopes, ("sst.mlp.forward", "sst.mlp.backward")) / total
