"""The paged-attention kernel's share of its roofline: the least time
the chip needs for the attention work the algorithm requires in the
traced window (``bench/work.py``: the larger of its FLOPs over peak
FLOP/s and its K/V bytes over peak bytes/s) over the kernel's device
time in that window, all chips summed."""

# the Pallas kernel's operation, as the TPU trace names it
KERNELS = (r"paged_attention", r"^_kernel")


def read(ctx):
    if ctx.trace is None or ctx.traced_work is None:
        return None
    s, n = ctx.trace.op_time(KERNELS)
    if not n or s <= 0:
        return None
    w, p = ctx.traced_work, ctx.peaks
    need = max(w.attn_flops / p["bf16_flops_per_s"],
               w.attn_bytes / p["hbm_bytes_per_s"])
    return 100.0 * need / s
