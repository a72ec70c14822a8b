"""Learner-plane scaling rows: train-step time + samples/sec vs D devices.

The multi-device learner (``distributed/learner.py``, DESIGN.md §9) shards
the learner batch over D mesh devices and all-reduces gradients with one
psum. This section records that trajectory: for each D in ``DS`` the same
ppo experiment is trained with ``Schedule.learner_devices=D`` and the
steady-state train-step time (min over post-compile iterations) lands in
``BENCH_<rev>.json`` as ``learner_ppo_D{d}`` with ``samples_per_sec`` and
``train_step_ms`` metrics.

Two further row families cover the pipelined FSDP learner (DESIGN.md §11):

* ``learner_ppo_fsdp_D{d}`` — params + Adam moments sharded per the
  ``_param_spec`` layout (``Schedule.fsdp``); the extra
  ``state_bytes_per_device`` metric is the peak live params+opt-state
  footprint of one device (sharded leaves count their shard only), so
  the ZeRO-3 memory win is recorded alongside the gather/reduce-scatter
  time cost.
* ``learner_ppo_overlap_{on,off}`` — the same D=4 FSDP experiment with
  and without the double-buffered collect/learn pipeline
  (``Schedule.overlap``); ``iter_ms`` is the measured steady-state
  wall-clock per iteration (the A/B ground truth) and the on-row's
  ``overlap_saved_s`` is the runner-accounted learn time hidden under
  collection per iteration.

Each config runs in its own subprocess because device fan-out must be
fixed *before* jax initialises: the child sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` at the top, ahead
of the jax import. On a real multi-core/multi-accelerator host the forced
host devices map to genuinely parallel compute and the rows measure
speedup; on a 1-core container they time-slice one core, so the rows
instead measure the sharding + collective *overhead* floor — either way
the trajectory is recorded per revision and ``run.py --compare`` can
flag regressions.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Dict, Sequence, Tuple

from benchmarks.common import emit

DS: Tuple[int, ...] = (1, 2, 4, 8)
FSDP_DS: Tuple[int, ...] = (2, 4, 8)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# child: force 8 host devices before jax import, train ppo with the
# sharded learner, report steady-state timings on one JSON line
_CHILD = r"""
import json, math, os, sys, time
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8"
                           ).strip()
import jax
from repro import experiment
from repro.experiment import ExperimentSpec, Schedule

d, iters, budget, env_batch, fsdp, overlap = map(int, sys.argv[1:7])
spec = ExperimentSpec(
    env="pendulum", algo="ppo", backend="inline", runtime="sync",
    model={"hidden": 64},
    schedule=Schedule(num_samplers=1, global_batch=env_batch,
                      horizon=budget // env_batch, seed=3,
                      learner_devices=(d if d > 1 else None),
                      fsdp=bool(fsdp), overlap=bool(overlap)))
runner = experiment.build(spec)


def bytes_per_device(tree):
    total = 0
    for leaf in jax.tree.leaves(tree):
        sh = getattr(leaf, "sharding", None)
        shape = (sh.shard_shape(leaf.shape) if sh is not None
                 else leaf.shape)
        total += math.prod(shape) * leaf.dtype.itemsize
    return total


try:
    runner.run(2)                    # jit compile (+ overlap learn_ref)
    t0 = time.perf_counter()
    logs = runner.run(iters)[-iters:]    # run() returns cumulative logs
    wall = time.perf_counter() - t0
finally:
    runner.close()
# under overlap the first 2 iterations of each run() call are the serial
# warmup; measure the pipelined (or, serial mode, post-compile) tail
steady = logs[2:] if overlap else logs[1:]
state_bytes = (bytes_per_device(runner.params)
               + bytes_per_device(runner.opt_state))
print("LEARNER_RESULT " + json.dumps(
    {"d": d, "learn_s": min(l.learn_time for l in steady),
     "samples": logs[0].samples,
     "iter_s": wall / iters,
     "saved_s": (sum(l.overlap_saved_s for l in steady) / len(steady)),
     "state_bytes": state_bytes}))
"""


def _require_cpu() -> None:
    """The children force host devices, so this section runs on the CPU
    backend only. On an accelerator this process already holds the chip
    (``benchmarks.common`` imports JAX), and a child would fail or hang
    trying to open it."""
    import jax
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"benchmarks/learner_scaling.py runs each D in a child process "
            f"on forced host devices; this process already holds the "
            f"{backend!r} backend, which a child cannot open. Run the "
            f"section with JAX_PLATFORMS=cpu; its rows are CPU timings, "
            f"not chip numbers.")


def _child(d: int, iterations: int, budget: int, env_batch: int,
           fsdp: bool = False, overlap: bool = False) -> dict:
    _require_cpu()
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   p for p in (os.path.join(REPO, "src"),
                               os.environ.get("PYTHONPATH", "")) if p))
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD, str(d), str(iterations),
         str(budget), str(env_batch), str(int(fsdp)), str(int(overlap))],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=900)
    if proc.returncode:
        raise RuntimeError(
            f"learner scaling child D={d} fsdp={fsdp} overlap={overlap} "
            f"failed:\n{proc.stderr[-2000:]}")
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("LEARNER_RESULT ")][-1]
    return json.loads(line.split(" ", 1)[1])


def sweep(ds: Sequence[int] = DS, iterations: int = 4, budget: int = 2048,
          env_batch: int = 16) -> Dict[int, float]:
    """samples/sec through the learner plane for each device count D."""
    out = {}
    for d in ds:
        rec = _child(d, iterations, budget, env_batch)
        sps = rec["samples"] / rec["learn_s"]
        emit(f"learner_ppo_D{d}", rec["learn_s"] * 1e6,
             f"samples_per_sec={sps:.0f} "
             f"train_step_ms={rec['learn_s'] * 1e3:.2f} "
             f"d={d} budget={budget}")
        out[d] = sps
    return out


def sweep_fsdp(ds: Sequence[int] = FSDP_DS, iterations: int = 4,
               budget: int = 2048, env_batch: int = 16) -> Dict[int, float]:
    """The FSDP layout's time + per-device memory trajectory vs D."""
    out = {}
    for d in ds:
        rec = _child(d, iterations, budget, env_batch, fsdp=True)
        sps = rec["samples"] / rec["learn_s"]
        emit(f"learner_ppo_fsdp_D{d}", rec["learn_s"] * 1e6,
             f"samples_per_sec={sps:.0f} "
             f"train_step_ms={rec['learn_s'] * 1e3:.2f} "
             f"state_bytes_per_device={rec['state_bytes']} "
             f"d={d} budget={budget}")
        out[d] = sps
    return out


def sweep_overlap(d: int = 4, iterations: int = 8, budget: int = 2048,
                  env_batch: int = 16) -> Dict[str, float]:
    """A/B the double-buffered pipeline against the serial schedule at
    fixed D (both FSDP, so the only variable is the overlap)."""
    out = {}
    for name, overlap in (("off", False), ("on", True)):
        rec = _child(d, iterations, budget, env_batch, fsdp=True,
                     overlap=overlap)
        sps = rec["samples"] / rec["iter_s"]
        derived = (f"iter_ms={rec['iter_s'] * 1e3:.2f} "
                   f"samples_per_sec={sps:.0f} d={d} budget={budget}")
        if overlap:
            derived += f" overlap_saved_s={rec['saved_s']:.6f}"
        emit(f"learner_ppo_overlap_{name}", rec["iter_s"] * 1e6, derived)
        out[name] = rec["iter_s"]
    return out


def run_all(ds: Sequence[int] = DS) -> Dict[int, float]:
    out = sweep(ds=ds)
    sweep_fsdp()
    sweep_overlap()
    return out


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--ds", default=",".join(map(str, DS)))
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run_all(ds=tuple(int(d) for d in args.ds.split(",")))
