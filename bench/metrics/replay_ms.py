"""Device milliseconds per learner update spent in the replay layer: the
ops inside the traced window whose name or text carries one of the
program's ``replay.*`` scopes (the buffer's add, sample and priority
write-back in ``algos.api.make_train_step``, and the sum-tree and ring
kernels' dispatchers), as a union per chip so that an op nested in
another counts once, over the window's updates (iterations x the mix's
``updates_per_collect``). A scope counts as a whole name: ``replay.find``
in an op named ``%replay.find.1`` or in
``op_name="jit(f)/replay.sample/..."``, not in a source path such as
``data/replay.py``. Nothing to read where no op carries such a scope."""
import re

from bench import tracing

SCOPES = ("replay.add", "replay.sample", "replay.update_priorities",
          "replay.find", "replay.update", "replay.gather", "replay.insert")
SCOPE = re.compile(r"(?<![\w.])(?:%s)(?!\w)"
                   % "|".join(re.escape(s) for s in SCOPES))


def read(ctx):
    lo, hi = ctx.trace.window
    seconds = 1e-9 * sum(
        tracing.union_length(((s, e) for n, s, e, t in ops
                              if SCOPE.search(n) or SCOPE.search(t)),
                             lo, hi)
        for ops in ctx.trace.devices.values())
    if seconds <= 0.0:
        return None
    updates = ctx.window["iterations"] * int(
        ctx.cell.traffic.get("updates_per_collect", 1))
    return 1e3 * seconds / updates
