"""Device time of one fused engine decode step (``_decode_core``): its
program's device time in the traced window over its executions."""

PROGRAMS = ("jit__decode_core",)


def read(ctx):
    if ctx.trace is None:
        return None
    s, n = ctx.trace.program_time(PROGRAMS)
    return 1e3 * s / n if n else None
