"""masked_dw: the compact weight gradient of the selected channel blocks,
dW[:, sel] = x^T dY[:, sel], one call per selectable leaf and trainable
layer. Call: m rows (tokens), k fan-in, cols = n_sel * block selected
output channels, itemsize of x and dY; the result is float32.

Least work: 2 m k cols FLOPs; x and the selected dY columns read once, the
float32 result written once."""

NAMES = ("masked_dw", "masked_dw_pipelined")


def cost(call: dict) -> tuple:
    m, k, cols, isz = call["m"], call["k"], call["cols"], call["itemsize"]
    return 2 * m * k * cols, (m * k + m * cols) * isz + k * cols * 4
