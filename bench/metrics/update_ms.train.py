"""update_ms.train: device time per train step of the sparse update
outside the model: the in-graph block draw, gathering the selected
blocks, the optimizer rule (fused_block_opt) and the write-back
(scatter_blocks) (ops under the program's `reselect` and `update`
scopes), self time from the trace."""
from bench import trace_scopes


def read(ctx):
    return trace_scopes.step_ms(ctx, ("reselect", "update"))
