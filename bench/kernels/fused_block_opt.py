"""fused_block_opt: the optimizer rule on the selected blocks of one
stacked leaf, in place: gather, SGD/momentum/AdamW rule, write-back.
Call: rows (trainable layers x fan-in), cols = n_sel * block, itemsize of
the weight and of its compact gradient, state = float32 state tensors
(0 SGD, 1 momentum, 2 AdamW).

Least work per element: weight read and written, gradient read, each
state tensor read and written; FLOPs 2 (SGD), 4 (momentum), 14 (AdamW:
two moments, two bias corrections, root, divide, update)."""

NAMES = ("fused_block_opt",)
FLOPS_PER_ELEMENT = {0: 2, 1: 4, 2: 14}


def cost(call: dict) -> tuple:
    n = call["rows"] * call["cols"]
    isz, st = call["itemsize"], call["state"]
    return FLOPS_PER_ELEMENT[st] * n, n * (3 * isz + 8 * st)
