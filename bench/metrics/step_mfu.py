"""The whole step's share of the chips' peak: FLOPs the algorithm
requires for the work dispatched in the traced window (``bench/work.py``)
over the window's length times the chips' peak bf16 FLOP/s."""


def read(ctx):
    if ctx.trace is None or ctx.traced_work is None or \
            ctx.trace.window_s <= 0:
        return None
    peak = ctx.peaks["bf16_flops_per_s"] * ctx.chips
    return 100.0 * ctx.traced_work.flops / (ctx.trace.window_s * peak)
