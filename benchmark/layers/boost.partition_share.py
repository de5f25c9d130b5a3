"""``sst.tree.partition`` device seconds (a tree's rows sorted into node
order, the level's items, the sorted copy of codes and statistics gathered
for the kernel) over the seconds of the whole stage loop
(``boost.device_s``) in the traced search: what a stage pays to put rows in
order that are all in the root."""


def read(ctx):
    return ctx["load_named"]("layers/boost.device_s:share")(
        ctx, "sst.tree.partition")
