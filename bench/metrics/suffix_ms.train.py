"""suffix_ms.train: device time per train step of the trainable suffix,
forward, recompute and backward with the masked_dw / batched_dw weight
gradients (ops under the program's `trainable_layers` scope), self time
from the trace."""
from bench import trace_scopes


def read(ctx):
    return trace_scopes.step_ms(ctx, ("trainable_layers",))
