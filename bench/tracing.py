"""From a profiler trace to the numbers the per-layer metrics read.

``profile`` runs a block under JAX's profiler (Python tracer off, so
the host's own cost stays small) and yields ``TraceAnnotation`` for the
harness's spans. ``load`` reads the ``.xplane.pb`` it wrote with
``jax.profiler.ProfileData`` and keeps, per device, the intervals in
which an operation ran, and on the host the harness's ``bench.*`` spans
and the runtime's own events. Everything after loading works on plain
``(name, start_ns, end_ns)`` tuples (``Trace``), so the tests can feed it
a small recorded trace.

* busy: the union of a device's op intervals inside the harness's
  ``bench.window`` span; ``busy_s`` is its mean over the chips used,
  ``window_s`` the span's length;
* kernel time: the summed durations, over every chip, of the ops that
  name the kernel or, since a TPU trace names a Pallas custom call
  ``closed_call.N`` without its kernel, whose operands match the
  kernel's signature (``pallas_operands``);
* ``breakdown``: the ten innermost ops that took the most device time,
  and the ten longest gaps in which the first chip ran nothing, each
  named by the innermost host event around the gap's middle.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

Span = Tuple[str, float, float]          # (name, start_ns, end_ns)
Op = Tuple[str, float, float, str]       # (name, start_ns, end_ns, text)

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)")
OPS_LINE = "XLA Ops"
TOP = 10


@contextlib.contextmanager
def profile(trace_dir: str):
    """Trace the block into ``trace_dir``; yields the span annotator."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        yield jax.profiler.TraceAnnotation
    finally:
        jax.profiler.stop_trace()


# ---------------------------------------------------------- interval sums
def union_length(spans: Iterable[Tuple[float, float]],
                 lo: float, hi: float) -> float:
    """Length of the union of ``[start, end)`` intervals clipped to
    ``[lo, hi)``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in spans):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(spans: Iterable[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The stretches of ``[lo, hi)`` that no interval covers."""
    out, cursor = [], lo
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in spans):
        if e <= s:
            continue
        if s > cursor:
            out.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        out.append((cursor, hi))
    return out


def innermost(host: Sequence[Span], t: float) -> str:
    """Name of the shortest host event that spans time ``t``."""
    best, best_len = "host: no event", None
    for name, s, e in host:
        if s <= t < e and (best_len is None or e - s < best_len):
            best, best_len = name, e - s
    return best


# ------------------------------------------------------------ the trace
@dataclasses.dataclass
class Trace:
    """A trace reduced to spans: ``devices`` maps a chip to its ops
    (``(name, start, end, text)``, the text being what the trace says of
    the op besides its name: program, module, source), ``host`` holds
    host events."""
    devices: Dict[str, List[Op]]
    host: List[Span]

    @property
    def window(self) -> Tuple[float, float]:
        spans = [(s, e) for n, s, e in self.host if n == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
        return spans[0]

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) * 1e-9

    def busy_by_device(self) -> Dict[str, float]:
        lo, hi = self.window
        return {d: union_length(((s, e) for _, s, e, _ in ops), lo, hi)
                * 1e-9
                for d, ops in self.devices.items()}

    @property
    def busy_s(self) -> float:
        busy = self.busy_by_device()
        return sum(busy.values()) / len(busy)

    def op_seconds(self, match) -> float:
        """Summed device seconds, over every chip, of the ops inside the
        window for which ``match(name, text)`` holds."""
        lo, hi = self.window
        return sum((min(e, hi) - max(s, lo)) * 1e-9
                   for ops in self.devices.values() for n, s, e, t in ops
                   if e > lo and s < hi and match(n, t))

    def kernel_seconds(self, kernel: str, signature=None) -> float:
        """Device seconds of the Pallas kernel ``kernel``: the custom
        calls whose text names it or, where the trace names none, whose
        operands ``signature`` accepts (a list of ``(dtype, dims)``)."""
        def match(name, text):
            if kernel in name or kernel in text:
                return True
            operands = pallas_operands(name)
            return (signature is not None and operands is not None
                    and signature(operands))
        return self.op_seconds(match)

    def breakdown(self) -> dict:
        """The ten innermost ops (those that hold no other op, so a loop
        does not stand in for its body) that took the most device time,
        averaged over the chips, and the ten longest idle gaps of the
        first chip, each named by the innermost host event around it."""
        lo, hi = self.window
        by_op = defaultdict(float)
        for ops in self.devices.values():
            ops = sorted(ops, key=lambda op: op[1])
            for i, (n, s, e, _) in enumerate(ops):
                holds = i + 1 < len(ops) and ops[i + 1][1] < e
                if e > lo and s < hi and not holds:
                    by_op[label(n)] += (min(e, hi) - max(s, lo)) * 1e-9
        n_dev = len(self.devices)
        top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
        first = sorted(self.devices)[0]
        idle = gaps(((s, e) for _, s, e, _ in self.devices[first]), lo, hi)
        idle = sorted(idle, key=lambda g: g[0] - g[1])[:TOP]
        return {"device_ops": [[n, s / n_dev] for n, s in top_ops],
                "idle_gaps": [[innermost(self.host, (s + e) / 2),
                               (e - s) * 1e-9] for s, e in idle]}


# ------------------------------------------------------- naming ops
HLO_OP = re.compile(r"%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")
LAYOUT = re.compile(r"\{[^{}]*\}")
TARGET = re.compile(r'custom_call_target="([^"]+)"')
SHAPE = re.compile(r"([a-z]+\d*)\[([\d,]*)\]")


def label(hlo: str) -> str:
    """A short name for a device op from its HLO text: instruction name,
    opcode (with a custom call's target) and result shape, layouts
    dropped."""
    m = HLO_OP.match(hlo)
    if not m:
        return hlo[:80]
    name, shape, opcode = m.groups()
    target = TARGET.search(hlo)
    if target:
        opcode = f"{opcode}:{target.group(1)}"
    return f"{name} {opcode} {LAYOUT.sub('', shape)[:60]}"


def pallas_operands(hlo: str):
    """``[(dtype, dims), ...]`` of a Pallas (``tpu_custom_call``) op's
    operands, read from its layout constraints; None for any other op."""
    if 'custom_call_target="tpu_custom_call"' not in hlo:
        return None
    start = hlo.find("operand_layout_constraints={")
    if start < 0:
        return None
    body = hlo[start + len("operand_layout_constraints={"):]
    depth, end = 1, 0
    for end, ch in enumerate(body):
        depth += {"{": 1, "}": -1}.get(ch, 0)
        if depth == 0:
            break
    body = LAYOUT.sub("", body[:end])
    return [(dtype, tuple(int(d) for d in dims.split(",") if d))
            for dtype, dims in SHAPE.findall(body)]


# ------------------------------------------------------------- loading
def _text(event) -> str:
    """Every string stat of an event, joined: where a trace keeps what it
    says of an op besides its name."""
    return " ".join(str(v) for _, v in event.stats if isinstance(v, str))


def load(trace_dir: str, chips: int) -> Trace:
    """Read the profiler's ``.xplane.pb`` under ``trace_dir``: the first
    ``chips`` TPU devices' op lines, and every host line."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices, host = {}, []
    texts: Dict[str, str] = {}
    for plane in data.planes:
        chip = DEVICE_PLANE.fullmatch(plane.name)
        if chip:
            if int(chip.group(1)) >= chips:
                continue
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    text = _text(ev)
                    text = texts.setdefault(text, text)     # one copy each
                    start = float(ev.start_ns)
                    ops.append((ev.name, start, start + ev.duration_ns,
                                text))
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    start = float(ev.start_ns)
                    host.append((ev.name, start, start + ev.duration_ns))
    if len(devices) < chips:
        raise ValueError(f"the trace holds {len(devices)} TPU devices, "
                         f"the cell uses {chips}")
    return Trace(devices, host)


@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric's reader gets: the cell, the traced
    window's counts and the runner's logs for it, the reduced trace, and
    the benchmark's directory (for ``work/`` and ``peaks.json``)."""
    cell: object
    window: dict
    logs: list
    trace: Trace
    bench: object
    device_kind: str
