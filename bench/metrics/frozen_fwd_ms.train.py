"""frozen_fwd_ms.train: device time per train step of the frozen prefix's
forward (ops under the program's `frozen_layers` scope), self time from
the trace."""
from bench import trace_scopes


def read(ctx):
    return trace_scopes.step_ms(ctx, ("frozen_layers",))
