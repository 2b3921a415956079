"""batched_dw: masked_dw for stacked expert weights, one call for all
experts. Call: e experts, c rows per expert, k fan-in, cols = n_sel *
block, itemsize of x and dY; the result is float32.

Least work: 2 e c k cols FLOPs; x and the selected dY columns read once,
the float32 result written once."""

NAMES = ("batched_dw", "batched_dw_pipelined")


def cost(call: dict) -> tuple:
    e, c, k, cols, isz = (call["e"], call["c"], call["k"], call["cols"],
                          call["itemsize"])
    return 2 * e * c * k * cols, (e * c * (k + cols)) * isz + e * k * cols * 4
