"""Milliseconds per call a fused runner spends reading the chunk's
metrics back to the host (the ``runner.pull`` span, after the chunk's
``block_until_ready``): the program's own span, summed over the traced
window's logs, over its calls. Nothing to read where the runner records
no such span."""
from bench import spans


def read(ctx):
    seconds = spans.span_seconds(ctx.logs, "runner.pull")
    if seconds is None:
        return None
    return 1e3 * seconds / ctx.window["calls"]
