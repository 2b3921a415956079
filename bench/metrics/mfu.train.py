"""mfu.train: the whole training step's share of the chip's bf16 peak.
The configuration's required FLOPs per token (bench/reference/<arch>.py
flops_per_token: forward of every layer, input gradients through the
trainable suffix and the head, weight gradients of the selected blocks;
recompute not counted) times the window's tokens/s, over chips x peak."""


def read(ctx):
    if not ctx.get("tokens_per_s") or not ctx.get("flops_per_token"):
        return None
    peak = ctx["chips"] * ctx["peak"]["bf16_flops_per_s"]
    return 100.0 * ctx["flops_per_token"] * ctx["tokens_per_s"] / peak
