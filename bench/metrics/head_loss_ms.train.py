"""head_loss_ms.train: device time per train step of the final norm, the
head matmul and the chunked cross-entropy, forward and backward (ops
under the program's `head_loss` scope), self time from the trace."""
from bench import trace_scopes


def read(ctx):
    return trace_scopes.step_ms(ctx, ("head_loss",))
