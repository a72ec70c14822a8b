"""Milliseconds a stepped runner's iteration spends in ``runner.log``
(``assemble_log``: the trajectory's episode returns and the host read
of their mean), averaged over the traced window's iterations: the
program's own span (``IterationLog.spans``). Nothing to read where the
runner records no spans."""
from bench import spans


def read(ctx):
    seconds = spans.span_seconds(ctx.logs, "runner.log")
    if seconds is None:
        return None
    return 1e3 * seconds / len(ctx.logs)
