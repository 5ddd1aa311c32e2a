"""Device time of admission (``_admit``, and ``_extend`` under chunked
prefill) per request admitted in the traced window."""

PROGRAMS = ("jit__admit", "jit__extend")


def read(ctx):
    if ctx.trace is None or not ctx.traced_admissions:
        return None
    s, n = ctx.trace.program_time(PROGRAMS)
    return 1e3 * s / ctx.traced_admissions if n else None
