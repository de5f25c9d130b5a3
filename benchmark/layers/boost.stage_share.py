"""``sst.boost.*`` device seconds (``.gradient``: the loss's mean, each
row's gradient and hessian, the stage's row weights; ``.update``: F +=
learning_rate x leaf) over the seconds of the whole stage loop
(``boost.device_s``) in the traced search: what a stage costs between its
trees."""


def read(ctx):
    return ctx["load_named"]("layers/boost.device_s:share")(
        ctx, "sst.boost.")
