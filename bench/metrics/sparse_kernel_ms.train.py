"""sparse_kernel_ms.train: device time of the sparse-update kernels
(masked_dw, batched_dw, fused_block_opt, scatter_blocks) per train step,
from the trace."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr.get("steps"):
        return None
    spent = sum(tr["kernel_s"].get(n, 0.0)
                for mod in ctx["kernel_costs"].values() for n in mod.NAMES)
    if spent <= 0:
        return None
    return 1000.0 * spent / tr["steps"]
