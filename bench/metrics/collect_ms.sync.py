"""Milliseconds a stepped runner's iteration spends collecting: the
system's own ``IterationLog.collect_time_serial`` (host clock around each
sampler's rollout, blocked), averaged over the traced window's
iterations. Nothing to read where the runner logs no collect time."""


def read(ctx):
    times = [log.collect_time_serial for log in ctx.logs]
    if not times or not any(times):
        return None
    return 1e3 * sum(times) / len(times)
