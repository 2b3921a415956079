"""sparse_kernel_roofline.train: the sparse-update kernels' share of their
roofline. Per step, the least time of each kernel call (the larger of its
FLOPs over the bf16 peak and its bytes over the HBM bandwidth, from
bench/kernels/<kernel>.py and the call's shapes), summed over the traced
steps, over the device time of those kernels' ops in the trace."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr.get("steps"):
        return None
    costs = ctx["kernel_costs"]
    spent = sum(tr["kernel_s"].get(n, 0.0)
                for mod in costs.values() for n in mod.NAMES)
    if spent <= 0:
        return None
    least = 0.0
    for kernel, call in ctx["kernel_calls"]:
        flops, nbytes = costs[kernel].cost(call)
        least += max(flops / ctx["peak"]["bf16_flops_per_s"],
                     nbytes / ctx["peak"]["hbm_bytes_per_s"])
    return 100.0 * least * tr["steps"] / spent
