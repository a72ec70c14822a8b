"""XLA backend compiles per iteration inside the traced window, wherever
in the runner they happen: the program's ``compiles@<span>`` counters
(``IterationLog.counts``) summed over the window's logs, over its
iterations. A warm runner reads 0. Nothing to read where the runner
keeps no counts."""
from bench import spans


def read(ctx):
    compiles = spans.count(ctx.logs, "compiles")
    if compiles is None:
        return None
    return compiles / ctx.window["iterations"]
