"""moe_dispatch_ms.train: device time per train step of the MoE layers'
routing and dispatch (ops under the program's `router` scope: scores, bias,
top-k; and `dispatch`: the sort of the routing slots by expert, the gather
of their rows and the weighted combine), in both stacks and directions,
self time from the trace."""
from bench import trace_scopes


def read(ctx):
    return trace_scopes.step_ms(ctx, ("router", "dispatch"))
