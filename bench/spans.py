"""What the per-layer readers take from the program's own tracer
(``repro.core.timing``): the span seconds and counts each
``IterationLog`` of the traced window holds.

A program without the tracer (logs with no ``spans`` or ``counts``)
leaves nothing to read: each function then returns None, and the reader
reports nothing."""
from __future__ import annotations

from typing import Optional


def span_seconds(logs, name: str) -> Optional[float]:
    """Seconds of the span ``name`` summed over ``logs``; None where no
    log holds it."""
    found = [log.spans[name] for log in logs
             if name in getattr(log, "spans", {})]
    return sum(found) if found else None


def count(logs, counter: str) -> Optional[int]:
    """``counter`` summed over ``logs`` and every span it was counted
    at (``<counter>@<span>``); None where the logs keep no counts."""
    if not any(hasattr(log, "counts") for log in logs):
        return None
    return sum(n for log in logs for key, n in log.counts.items()
               if key.split("@")[0] == counter)
