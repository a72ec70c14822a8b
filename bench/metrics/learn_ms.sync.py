"""Milliseconds a stepped runner's iteration spends learning: the
system's own ``IterationLog.learn_time`` (host clock around the blocked
train step), averaged over the traced window's iterations. Nothing to
read where the runner has no collect/learn boundary."""


def read(ctx):
    if not any(log.collect_time_serial for log in ctx.logs):
        return None
    return 1e3 * sum(log.learn_time for log in ctx.logs) / len(ctx.logs)
