"""expert_ffn_ms.train: device time per train step of the held experts'
SwiGLU (ops under the program's `experts` scope: the grouped matmuls in
both directions and, for the trainable layers, the `batched_dw` kernel of
the selected blocks), self time from the trace."""
from bench import trace_scopes


def read(ctx):
    return trace_scopes.step_ms(ctx, ("experts",))
