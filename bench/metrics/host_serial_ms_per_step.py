"""Host time the scheduler spends with no step in flight, per engine
step: the change of ``pipeline_stats()["serial_host_s"]`` over the
window, over the engine steps taken in it (summed over replicas)."""


def read(ctx):
    steps = ctx.counters["engine_steps"]
    return 1e3 * ctx.counters["serial_host_s"] / steps if steps else None
