"""The benchmark harness: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``BENCHMARK.json``'s ``workloads``: a configuration
file, a traffic file and a number of chips. The harness finds each file by
the name the entry gives, so a cell, a mix or a metric is added as files
and entries, never by editing this one.

A run:

1. refuses, before printing any result, when JAX finds no TPU or fewer
   chips than the cell asks for;
2. set-up (``setup_s``, from process start): builds the system's runner
   through ``experiment.build``, makes what the mix asks for (a replay
   filled to capacity), and drives the runner through its first
   ``CHECK_STEPS`` calls, the same calls the window makes. They compile
   every program the window uses, and what they leave behind (losses,
   Adam's state after the first, the parameters after the last and, for
   a stepped runtime, each call's trajectory) is what the reference is
   compared with;
3. the window: calls the runner until ``--seconds`` have passed, each call
   ending in ``block_until_ready``; ``env_steps_per_s`` is every env-step
   collected and trained on over the whole window. With ``--trace 1`` the
   window runs under the profiler for the mix's ``trace_seconds`` instead,
   and the cell's per-layer metrics are read from it;
4. reads the device's peak memory, frees the system's state and runs the
   configuration's plain reference from the seed through the same steps,
   then compares (``reflib.compare``) against the cell's limits.

A reference that follows the program. The reference is the configuration's
file beside its JSON (``bench/configs/<config>.py``); it exports
``run(cfg, traffic, seed, steps, **kw) -> {"losses", "grad", "change"}``
and ``program_observables(params, opt_state)``. Where the program draws
at random (sampled actions, sampled tokens), a reference that draws for
itself trains on other data as soon as one draw flips, and the comparison
then measures sampling noise. So a reference module may set
``FOLLOWS_PROGRAM = True``. In a stepped runtime (``sync``: one collect
-> learn iteration per call) the harness then keeps a host copy of the
merged trajectory each check call collected and calls
``run(cfg, traffic, seed, CHECK_STEPS, program=[traj_1, ..., traj_n])``,
each ``traj_k`` the dict of time-major arrays the runner's collect
returned in call ``k``. The rule for what a reference may take from it:

* what the program drew: the sampled actions; for a token policy, the
  drawn tokens and the prompts;
* what each step was fed: the observation, or the token context.

It recomputes everything else from those: each env step (next
observation, reward, done, with the step's env key by the system's key
rules), each step's behaviour log-prob and value, the advantages, the
minibatch epochs and the optimizer steps. It never copies a result the
program computed into its own computation. What it checks step by step
it returns under ``numbers`` (``{name: gap}``), which ``reflib.compare``
passes through beside its own numbers and a cell's limits file can name.
A reference that does not set the attribute, and any cell of the fused
runtime, is called without ``program``: a fused chunk's trajectory never
leaves the chunk's program, and keeping it would change the timed
program.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit.
The same numbers end standard error.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

ROOT = pathlib.Path(__file__).resolve().parent.parent
CHECK_STEPS = 3


class Refused(Exception):
    """The run cannot measure this cell here (no chip, unknown cell)."""


# ------------------------------------------------------------ finding cells
def load_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    """Import a benchmark file by its path (names may hold '-' and '.')."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    def __init__(self, benchmark: dict, name: str, root: pathlib.Path = ROOT):
        cells = {w["name"]: w for w in benchmark["workloads"]}
        if name not in cells:
            raise Refused(f"no workload {name!r} in BENCHMARK.json; choose "
                          f"from {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in benchmark["configs"]}
        self.config_entry = configs[self.entry["config"]]
        cfg_file = root / self.config_entry["file"]
        self.bench = root / "bench"
        self.config = load_json(cfg_file)
        self.reference_file = cfg_file.with_suffix(".py")
        self.traffic = load_json(self.bench / "traffic"
                                 / f"{self.entry['traffic']}.json")
        self.limits = load_json(self.bench / "limits" / f"{name}.json")
        self.end_to_end = [m for m in benchmark["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in benchmark["per_layer"]
                          if name in m.get("workloads", [name])]

    def reference(self):
        return load_module(self.reference_file)


# ------------------------------------------------------------- set-up
class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or loading from
    the persistent cache), by function, with the cache's hits and misses:
    where set-up goes, and whether anything compiles inside the window."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
              "/jax/core/compile/backend_compile_duration": "compile_s"}
    COUNTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
              "/jax/compilation_cache/cache_misses": "cache_misses"}

    def __init__(self, jax):
        self.totals = {v: 0.0 for v in self.EVENTS.values()}
        self.totals.update({v: 0 for v in self.COUNTS.values()})
        self.by_function: Dict[str, float] = {}
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        key = self.EVENTS.get(event)
        if key is None:
            return
        self.totals[key] += secs
        if key == "compile_s":
            self.compiles += 1
        name = f"{key}:{kw.get('fun_name', '?')}"
        self.by_function[name] = self.by_function.get(name, 0.0) + secs

    def _event(self, event, **_):
        key = self.COUNTS.get(event)
        if key is not None:
            self.totals[key] += 1

    def summary(self, top: int = 8) -> dict:
        slowest = sorted(self.by_function.items(), key=lambda kv: -kv[1])
        return {**self.totals, "slowest": slowest[:top]}


# ------------------------------------------------------------ the system
class Caller:
    """Calls the system's runner one measured unit at a time and keeps
    what the correctness check reads from the first calls.

    A unit is one ``runner.run`` call: a whole fused chunk (one
    dispatch), or one collect -> learn iteration of a stepped runner.
    The fused runner reports no loss, so its ``loop_for`` is wrapped to
    keep each call's loss array (the program it returns is the system's
    own, unchanged). A stepped (``sync``) runner's ``_collect`` is
    wrapped on the instance to keep a host copy of each check call's
    merged trajectory; ``release`` puts the runner's own method back
    before the window."""

    def __init__(self, runner, traffic: dict, loss_key: str):
        from bench import spec
        self.runner = runner
        self.iterations = spec.iterations_per_call(traffic)
        self.env_steps = self.iterations * spec.env_steps_per_iteration(
            traffic)
        self.loss_key = loss_key
        self.keeping = False
        self._losses: List[Any] = []
        self.trajectories: List[Any] = []
        if traffic["runtime"] == "fused":
            inner = runner.loop_for

            def loop_for(chunk):
                loop = inner(chunk)

                def kept(state):
                    state, metrics = loop(state)
                    if self.keeping:
                        self._losses.append(metrics[loss_key])
                    return state, metrics
                return kept

            runner.loop_for = loop_for
        elif traffic["runtime"] == "sync":
            import jax
            collect = runner._collect

            def kept_collect():
                merged, stats = collect()
                if self.keeping:
                    self.trajectories.append(jax.device_get(merged))
                return merged, stats

            runner._collect = kept_collect

    def release(self) -> None:
        """The runner's own ``_collect`` again: the window runs it
        untouched."""
        vars(self.runner).pop("_collect", None)

    def call(self) -> None:
        self.runner.run(self.iterations)

    def call_with_loss(self) -> float:
        """One call; the mean of its iterations' losses."""
        import numpy as np
        self.keeping = True
        self._losses.clear()
        try:
            self.call()
        finally:
            self.keeping = False
        if self._losses:
            return float(np.mean(np.asarray(self._losses[-1])))
        return float(np.asarray(self.runner.metrics[self.loss_key]))


def check_steps(caller: Caller, reference_module, steps: int) -> dict:
    """Drive the first ``steps`` calls; keep the observables the
    reference is compared on (as host arrays, so nothing the program
    holds is kept), then release the caller's hold on the runner."""
    from bench import reflib
    params0 = reflib.leaves(caller.runner.params)
    losses, grad = [], None
    for step in range(steps):
        losses.append(caller.call_with_loss())
        if step == 0:
            _, grad = reference_module.program_observables(
                caller.runner.params, caller.runner.opt_state)
    caller.release()
    params, _ = reference_module.program_observables(
        caller.runner.params, caller.runner.opt_state)
    observed = {"losses": losses, "grad": grad,
                "change": reflib.change_norms(params0, params)}
    if caller.trajectories:
        observed["trajectories"] = caller.trajectories
    return observed


def run_reference(reference_module, cfg: dict, traffic: dict, seed: int,
                  observed: dict, **kwargs) -> dict:
    """The reference over the check calls. One that follows the program
    (``FOLLOWS_PROGRAM``) is handed the check calls' trajectories where
    ``observed`` has them; any other is called without them."""
    if (getattr(reference_module, "FOLLOWS_PROGRAM", False)
            and observed.get("trajectories")):
        kwargs["program"] = observed["trajectories"]
    return reference_module.run(cfg, traffic, seed, CHECK_STEPS, **kwargs)


def fill_replay(runner, cfg: dict, traffic: dict, seed: int) -> None:
    """Fill the runner's replay through the buffer's own insert with the
    mix's transitions (``spec.replay_fill``)."""
    import jax
    from repro import registry
    from bench import spec
    buffer = registry.make("buffer", cfg["buffer"], **cfg["buffer_kwargs"])
    buffer.gamma = float(cfg["algo_kwargs"]["gamma"])
    traj = spec.replay_fill(traffic["replay_fill"], seed)
    buf_state, key = runner.state.plane_state
    buf_state = jax.jit(buffer.add, donate_argnums=(0,))(buf_state, traj)
    runner.state = runner.state._replace(plane_state=(buf_state, key))
    del traj
    jax.block_until_ready(runner.state.plane_state)


# ------------------------------------------------------------- the window
def measure(caller: Caller, seconds: float,
            annotate: Optional[Callable] = None) -> dict:
    """Call until ``seconds`` have passed; whole calls only."""
    calls = 0
    t0 = time.perf_counter()
    while True:
        if annotate is None:
            caller.call()
        else:
            with annotate("bench.call"):
                caller.call()
        calls += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    return {"start": t0, "seconds": elapsed, "calls": calls,
            "iterations": calls * caller.iterations,
            "env_steps": calls * caller.env_steps}


def device_record(jax, chips: int) -> dict:
    devices = jax.devices()[:chips]
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


def enable_compile_cache(jax, root: pathlib.Path) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every executable kept, none evicted. Eviction (which
    ``JAX_COMPILATION_CACHE_MAX_SIZE`` turns on) keeps an access-time file
    beside every entry, and once one is missing every later write fails
    and every run compiles afresh; so it is off, and the directory is the
    benchmark's alone, so no other entry point's files meet its own."""
    path = root / ".jax_cache" / "bench"
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ----------------------------------------------------------------- a run
def timed_window(cell: Cell, caller: Caller, seconds: float,
                 t_start: float) -> tuple:
    """The end-to-end metrics over a ``seconds`` window."""
    window = measure(caller, seconds)
    values = {"env_steps_per_s": window["env_steps"] / window["seconds"],
              "setup_s": window["start"] - t_start}
    # a metric named ``<quantity>.<group>`` is the quantity, bounded apart
    return window, {m["name"]: {"value": values[m["name"].split(".")[0]],
                                "unit": m["unit"]}
                    for m in cell.end_to_end}


def traced_window(cell: Cell, caller: Caller, seconds: float,
                  device_kind: str) -> tuple:
    """The per-layer metrics over a window of the mix's
    ``trace_seconds`` under the profiler, and the reduced trace."""
    from bench import tracing
    seconds = min(seconds, float(cell.traffic.get("trace_seconds",
                                                  seconds)))
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        with tracing.profile(trace_dir) as annotate:
            with annotate(tracing.WINDOW_SPAN):
                window = measure(caller, seconds, annotate)
        reduced = tracing.load(trace_dir, cell.chips)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = tracing.MetricContext(
        cell=cell, window=window,
        logs=list(caller.runner.logs[-window["iterations"]:]),
        trace=reduced, bench=cell.bench, device_kind=device_kind)
    metrics = {}
    for m in cell.per_layer:
        value = load_module(cell.bench / "metrics"
                            / f"{m['name']}.py").read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return window, metrics, reduced


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             root: pathlib.Path = ROOT) -> dict:
    """One run of ``cell``: set-up, the window, the check. Returns the
    result line's object."""
    import jax
    import numpy as np
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise Refused(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < cell.chips:
        raise Refused(f"cell {cell.name!r} needs {cell.chips} chips, JAX "
                      f"found {len(devices)}")
    enable_compile_cache(jax, root)
    clock = CompileClock(jax)
    from repro import experiment
    from bench import reflib, spec

    cfg, traffic = cell.config, cell.traffic
    reference = cell.reference()
    runner = experiment.build(spec.experiment_spec(cfg, traffic, seed))
    if "replay_fill" in traffic:
        fill_replay(runner, cfg, traffic, seed)
    caller = Caller(runner, traffic, cfg["loss_key"])
    observed = check_steps(caller, reference, CHECK_STEPS)
    setup = clock.summary()

    compiles_before = clock.compiles
    out: Dict[str, Any] = {}
    if trace:
        window, metrics, reduced = traced_window(
            cell, caller, seconds, devices[0].device_kind)
    else:
        window, metrics = timed_window(cell, caller, seconds, t_start)
    window_compiles = clock.compiles - compiles_before
    finite = all(bool(np.all(np.isfinite(v)))
                 for v in reflib.leaves(runner.params).values())
    device = device_record(jax, cell.chips)
    if trace:
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)

    # the system's state goes before the reference runs on the chip
    del caller, runner
    gc.collect()
    numbers = reflib.compare(
        observed, run_reference(reference, cfg, traffic, seed, observed))
    checks = {k: {"value": numbers[k], "limit": limit}
              for k, limit in cell.limits.items()}
    attempted = window["iterations"]
    out = {"correct": finite and all(c["value"] <= c["limit"]
                                     for c in checks.values()),
           "attempted": attempted, "failed": 0 if finite else attempted,
           "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = reduced.breakdown()
    out["setup"] = setup
    out["compiles_in_window"] = window_compiles
    out["numbers"] = numbers
    out["checks"] = checks
    return out


def main(argv=None, *, t_start: Optional[float] = None,
         require_tpu: bool = True, root: pathlib.Path = ROOT) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = Cell(load_json(root / "BENCHMARK.json"), args.workload, root)
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       t_start=t_start, require_tpu=require_tpu, root=root)
    except (Refused, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(f"set-up: {json.dumps(out['setup'])}", file=sys.stderr)
    if out["compiles_in_window"]:
        print(f"bench: {out['compiles_in_window']} compilations inside the "
              f"window", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0
