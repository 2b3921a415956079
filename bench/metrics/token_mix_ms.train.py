"""token_mix_ms.train: device time per train step of the token mixers
(attention, or the wkv / ssm time mix) in both stacks and both directions
(ops under the program's `token_mix` scope), self time from the trace."""
from bench import trace_scopes


def read(ctx):
    return trace_scopes.step_ms(ctx, ("token_mix",))
