"""Chip smoke: the RL main path on a TPU, through the entry points users call.

Every phase runs through ``repro.experiment.run`` / ``experiment.build``
or ``serve.load_policy`` + ``PolicyServer``, with policies at their
published widths (walle-mlp: two hidden layers of 64) and envs and
weights generated from a fixed seed. Each phase prints one JSON line: its
name, the platform and device kind its arrays live on, cold and warm
compile seconds, run seconds, and each correctness check with its
tolerance and the value it measured. These are set-up and smoke timings
of a few iterations, not throughput.

    python3 chip_smoke.py               # one chip: paper, env_plane,
                                        # off_policy, actors, serve
    python3 chip_smoke.py --four-chips  # four chips: the data-parallel
                                        # learner at D=4 against D=1

The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
printed only when every phase passed on a TPU. The script exits non-zero
without it when JAX finds no TPU (``JAX_PLATFORMS=cpu`` included) or any
phase fails.

Cold compile seconds are what this process paid to lower and compile,
or to load from a persistent cache the machine already held (its hits
are counted). Warm compile seconds repeat the phase's step after
``jax.clear_caches()``, so they are what a later process pays with the
cache this run wrote.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import threading
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import experiment, registry  # noqa: E402
from repro.checkpoint import save  # noqa: E402
from repro.data.buffers import ring_gather, sumtree_find_batch  # noqa: E402
from repro.experiment import ExperimentSpec, Schedule  # noqa: E402
from repro.kernels import gae as gae_ops  # noqa: E402
from repro.kernels import select  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.serve import PolicyServer, load_policy  # noqa: E402

SEED = 0
WALLE_MLP = {"hidden": 64}          # configs/walle_mlp.py: 2 x 64
CKPT_DIR = ROOT / "smoke_out" / "paper_ckpt"    # .gitignore lists it

# lowering to StableHLO and XLA compilation (or a persistent-cache load);
# Python tracing is left out, since a jit traced inside another reports
# its own trace time within the outer one's
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(AssertionError):
    """A phase's correctness check did not hold."""


class _CompileClock:
    """Seconds JAX spends lowering and compiling (or loading from the
    persistent cache), and persistent-cache hits, summed since the
    process registered it."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += secs

    def _event(self, event, **_):
        if event == _CACHE_HIT:
            self.cache_hits += 1


_clock = None


def clock() -> _CompileClock:
    global _clock
    if _clock is None:
        _clock = _CompileClock()
    return _clock


class Phase:
    """One phase's record: placement, timings and checks."""

    def __init__(self, name: str):
        self.rec = {"phase": name}
        self.checks = []
        self.rec["checks"] = self.checks

    def timed(self, fn):
        """Run ``fn``; record its compile seconds (and the persistent
        cache's hits among them) and the rest as run seconds."""
        c0, h0 = clock().seconds, clock().cache_hits
        t0 = time.perf_counter()
        out = fn()
        wall = time.perf_counter() - t0
        compile_s = clock().seconds - c0
        self.rec["compile_s_cold"] = compile_s
        self.rec["cache_hits_cold"] = clock().cache_hits - h0
        self.rec["run_s"] = wall - compile_s
        return out

    def warm(self, fn) -> None:
        """Repeat ``fn`` with the in-memory caches dropped: its compile
        seconds are a later process's, given this run's persistent
        cache."""
        jax.clear_caches()
        c0, h0 = clock().seconds, clock().cache_hits
        fn()
        self.rec["compile_s_warm"] = clock().seconds - c0
        self.rec["cache_hits_warm"] = clock().cache_hits - h0

    def placed(self, tree) -> None:
        """Record the platform and device kind ``tree`` lives on."""
        devs = {d for leaf in jax.tree.leaves(tree) for d in leaf.devices()}
        self.rec["platform"] = ",".join(sorted({d.platform for d in devs}))
        self.rec["device_kind"] = ",".join(sorted({d.device_kind
                                                    for d in devs}))
        self.require("arrays on the default platform",
                     self.rec["platform"] == jax.devices()[0].platform,
                     tol="exact", value=self.rec["platform"])

    def require(self, name: str, ok: bool, *, tol, value=None) -> None:
        self.checks.append({"check": name, "ok": bool(ok), "tol": tol,
                            "value": value})
        if not ok:
            raise SmokeFailure(f"{self.rec['phase']}: {name} failed "
                               f"(tolerance {tol}, measured {value})")


def _finite(tree) -> bool:
    return all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(tree))


def _max_abs_diff(a, b) -> float:
    return max(float(np.max(np.abs(np.asarray(x, np.float64)
                                   - np.asarray(y, np.float64))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ==================================================================== paper
def phase_paper(ckpt_dir=CKPT_DIR, *, num_samplers: int = 10,
                horizon: int = 1000, iterations: int = 3) -> dict:
    """The paper's headline run: PPO on cheetah (the planar HalfCheetah
    stand-in), walle-mlp, N samplers on the sync inline runtime. Saves
    the checkpoint the serve phase loads."""
    ph = Phase("paper")
    spec = ExperimentSpec(
        env="cheetah", algo="ppo", backend="inline", runtime="sync",
        model=WALLE_MLP,
        schedule=Schedule(num_samplers=num_samplers,
                          global_batch=num_samplers, horizon=horizon,
                          iterations=iterations, seed=SEED))
    result = ph.timed(lambda: experiment.run(spec))
    ph.placed(result.params)
    ph.require("params finite", _finite(result.params), tol="all finite")
    ph.require("losses finite", _finite(result.runner.metrics),
               tol="all finite",
               value=float(jnp.mean(result.runner.metrics["loss"])))
    ph.rec["samples_per_iter"] = result.logs[-1].samples
    save(str(ckpt_dir), iterations, result.params,
         metadata={"mode": "rl", "spec": spec.to_dict()})
    ph.warm(lambda: experiment.run(spec, iterations=1))
    return ph.rec


# ================================================================ env plane
# A step of cheetah physics is a dozen f32 operations deep per leaf
# (sin included). The kernel and the reference are different
# executables, which may contract a multiply-add or evaluate sin
# differently by an ulp or two (1.2e-7 relative) per operation; a dozen
# of those on values of order 1 stay well inside 1e-5.
ENV_STEP_TOL = {"rtol": 1e-5, "atol": 1e-5}
# GAE is a T-step backward recurrence of f32 multiply-adds: each step may
# round differently by an ulp where one executable contracts a
# multiply-add and the other does not. Over T = 16 steps, with advantages
# and returns of order 10, that is at most ~16 x 10 x 1.2e-7 = 2e-5.
GAE_TOL = {"rtol": 1e-5, "atol": 1e-4}


def _env_step_pallas_vs_ref(batch: int, key) -> float:
    """One cheetah ``env_step`` through Pallas and through the reference
    at ``batch``, a quarter of the instances on their last step so the
    auto-reset path runs; returns the largest gap, scaled to the
    tolerance (<= 1 holds)."""
    env = registry.make("env", "cheetah")
    k1, k2, k3 = jax.random.split(key, 3)
    state, _ = jax.vmap(env.reset)(jax.random.split(k1, batch))
    last = jnp.arange(batch) % 4 == 0
    state = state[:4] + (jnp.where(last, env.max_episode_steps - 1,
                                   jax.random.randint(k2, (batch,), 0,
                                                      env.max_episode_steps)
                                   ).astype(jnp.int32),)
    reset_state, reset_obs = jax.vmap(env.reset)(jax.random.split(k3, batch))
    actions = jax.random.uniform(key, (batch, env.act_dim), minval=-1.0,
                                 maxval=1.0)
    out = {impl: jax.jit(lambda *a, impl=impl: env.batch_step(
        *a, impl=impl))(state, actions, None, reset_state, reset_obs)
        for impl in ("pallas", "ref")}
    worst = 0.0
    for p, r in zip(jax.tree.leaves(out["pallas"]),
                    jax.tree.leaves(out["ref"])):
        p, r = np.asarray(p), np.asarray(r)
        if not np.issubdtype(p.dtype, np.floating):
            if not np.array_equal(p, r):     # step counters and dones
                return float("inf")
            continue
        bound = ENV_STEP_TOL["atol"] + ENV_STEP_TOL["rtol"] * np.abs(r)
        worst = max(worst, float(np.max(np.abs(p - r) / bound)))
    return worst


def _gae_pallas_vs_ref(T: int, batch: int, key) -> float:
    """GAE over (T, batch) through Pallas and the reference; the largest
    gap scaled to the tolerance (<= 1 holds)."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    r = jax.random.normal(k1, (T, batch))
    v = 5.0 * jax.random.normal(k2, (T, batch))
    d = jax.random.bernoulli(k3, 0.05, (T, batch))
    lv = 5.0 * jax.random.normal(k4, (batch,))
    out = {impl: jax.jit(lambda *a, impl=impl: gae_ops.gae(*a, impl=impl))(
        r, v, d, lv) for impl in ("pallas", "ref")}
    worst = 0.0
    for p, q in zip(out["pallas"], out["ref"]):
        p, q = np.asarray(p), np.asarray(q)
        bound = GAE_TOL["atol"] + GAE_TOL["rtol"] * np.abs(q)
        worst = max(worst, float(np.max(np.abs(p - q) / bound)))
    return worst


def phase_env_plane(*, env_batch: int = 4096, horizon: int = 16,
                    iterations: int = 3, kernels: str = "auto") -> dict:
    """Massively parallel on-device PPO: one cheetah ``VectorEnv`` of
    ``env_batch`` instances on the fused runtime, env step and GAE
    through the kernels ``kernels`` selects."""
    ph = Phase("env_plane")
    spec = ExperimentSpec(
        env="cheetah", algo="ppo", runtime="fused", kernels=kernels,
        model=WALLE_MLP,
        schedule=Schedule(env_batch=env_batch, horizon=horizon,
                          iterations=iterations, seed=SEED))
    runner = experiment.build(spec)
    on_tpu = jax.devices()[0].platform == "tpu"
    resolved = {op: select.resolve(kernels, op)
                for op in ("env_step.step", "gae.gae")}
    ph.require("env_step and gae resolve to Pallas, compiled on TPU",
               all(r == ("pallas", not on_tpu) for r in resolved.values()),
               tol="exact", value={k: list(v) for k, v in resolved.items()})
    text = runner.loop_for(iterations).lower(runner.state).as_text()
    ph.require("lowered fused step holds tpu_custom_call on TPU",
               ("tpu_custom_call" in text) == on_tpu, tol="exact",
               value="tpu_custom_call" in text)
    logs = ph.timed(lambda: runner.run(iterations))
    ph.placed(runner.params)
    ph.require("params finite", _finite(runner.params), tol="all finite")
    ph.rec["samples_per_iter"] = logs[-1].samples
    key = jax.random.PRNGKey(SEED)
    gap = _env_step_pallas_vs_ref(env_batch, key)
    ph.require(f"env_step Pallas vs ref at B={env_batch} (gap / bound)",
               gap <= 1.0, tol=ENV_STEP_TOL, value=gap)
    gap = _gae_pallas_vs_ref(horizon, env_batch, key)
    ph.require(f"gae Pallas vs ref at (T, B)=({horizon}, {env_batch}) "
               f"(gap / bound)", gap <= 1.0, tol=GAE_TOL, value=gap)
    ph.warm(lambda: experiment.run(spec))
    return ph.rec


# =============================================================== off-policy
REPLAY_OPS = ("sum_tree.find", "sum_tree.update", "replay_ring.gather",
              "replay_ring.insert")


def phase_off_policy(*, capacity: int = 100_000, batch_size: int = 256,
                     global_batch: int = 16, horizon: int = 64,
                     iterations: int = 3, kernels: str = "auto") -> dict:
    """SAC on cheetah with the README's prioritized replay (capacity
    100k, minibatch 256, 3-step returns)."""
    ph = Phase("off_policy")
    spec = ExperimentSpec(
        env="cheetah", algo="sac", buffer="prioritized", kernels=kernels,
        model=WALLE_MLP,
        buffer_kwargs={"capacity": capacity, "batch_size": batch_size,
                       "n_step": 3},
        schedule=Schedule(num_samplers=1, global_batch=global_batch,
                          horizon=horizon, iterations=iterations,
                          seed=SEED))
    result = ph.timed(lambda: experiment.run(spec))
    ph.placed(result.params)
    ph.require("params finite", _finite(result.params), tol="all finite")
    ph.rec["resolved"] = {op: list(select.resolve(kernels, op))
                          for op in REPLAY_OPS}
    if jax.devices()[0].platform == "tpu":
        ph.require("compiled Pallas, or ref where select.TPU_REFUSED says",
                   ph.rec["resolved"] == {
                       op: ["ref" if op in select.TPU_REFUSED else "pallas",
                            False] for op in REPLAY_OPS},
                   tol="exact", value=ph.rec["resolved"])
    # the kernels against the reference on the run's own replay state:
    # the descent compares and subtracts the same f32 values, the gather
    # moves bytes, so both agree exactly
    state = result.runner.buffer_state
    u = (jnp.arange(batch_size, dtype=jnp.float32) + 0.5) / batch_size
    masses = u * state.tree.total
    idx = {impl: jax.jit(lambda t, m, impl=impl: sumtree_find_batch(
        t, m, impl=impl))(state.tree, masses) for impl in ("pallas", "ref")}
    ph.require(f"sum-tree find Pallas == ref at capacity "
               f"{state.tree.levels[0].shape[0]}",
               np.array_equal(idx["pallas"], idx["ref"]), tol="exact",
               value=int(np.sum(np.asarray(idx["pallas"])
                                != np.asarray(idx["ref"]))))
    rows = {impl: jax.jit(lambda s, i, impl=impl: ring_gather(
        s, i, impl=impl))(state.ring.storage, idx["ref"])
        for impl in ("pallas", "ref")}
    gap = _max_abs_diff(rows["pallas"], rows["ref"])
    ph.require("ring gather Pallas == ref", gap == 0.0, tol="exact",
               value=gap)
    ph.warm(lambda: experiment.run(spec, iterations=1))
    return ph.rec


# =================================================================== actors
def phase_actors(*, workers: int = 2, global_batch: int = 8,
                 horizon: int = 200, iterations: int = 2) -> dict:
    """CPU rollout worker processes feeding the learner on the chip."""
    ph = Phase("actors")
    spec = ExperimentSpec(
        env="cheetah", algo="ppo", backend="process", model=WALLE_MLP,
        schedule=Schedule(num_samplers=workers, num_workers=workers,
                          global_batch=global_batch, horizon=horizon,
                          iterations=iterations, seed=SEED))
    result = ph.timed(lambda: experiment.run(spec))
    ph.placed(result.params)
    ph.require("params finite", _finite(result.params), tol="all finite")
    reported = dict(result.runner.backend.pool.worker_platforms)
    ph.require("every worker reports the CPU platform",
               sorted(reported) == list(range(workers))
               and set(reported.values()) == {"cpu"}, tol="exact",
               value=reported)
    ph.warm(lambda: experiment.run(spec, iterations=1))
    return ph.rec


# ==================================================================== serve
# Every action is served by the same compiled executable as its
# single-request reference (occupancy 1 against up to ``slots``); rows of
# a batched MLP are independent, so they agree to within rounding that
# depends on nothing but the row itself.
SERVE_ATOL = 1e-6


def phase_serve(ckpt_dir=CKPT_DIR, *, slots: int = 8, requests: int = 64,
                clients: int = 4) -> dict:
    """The paper checkpoint behind a ``PolicyServer``, answering
    concurrent clients."""
    ph = Phase("serve")
    handle = load_policy(str(ckpt_dir))
    ph.placed(handle.params)
    obs = np.random.default_rng(SEED).normal(
        size=(requests, handle.env.obs_dim)).astype(np.float32)
    done = []
    lock = threading.Lock()

    def client(c):
        got = [(i, server.submit(obs[i]))
               for i in range(c, requests, clients)]
        for _, pending in got:
            pending.result(timeout=60.0)
        with lock:
            done.extend(got)

    def serve():
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    server = PolicyServer(handle.env, handle.algo, handle.params,
                          slots=slots, seed=SEED)
    ph.timed(lambda: (server.start(), serve(), server.close()))
    ph.require(f"all {requests} requests answered", len(done) == requests,
               tol="exact", value=len(done))
    gap = max(float(np.max(np.abs(p.action - server.reference_act(
        obs[i], p.key)))) for i, p in done)
    ph.require("every action matches reference_act", gap <= SERVE_ATOL,
               tol={"atol": SERVE_ATOL}, value=gap)
    ph.require("every completion reports a params_version",
               all(p.params_version is not None for _, p in done),
               tol="exact",
               value=sorted({p.params_version for _, p in done}))
    ph.warm(lambda: PolicyServer(handle.env, handle.algo, handle.params,
                                 slots=slots, seed=SEED).start().close())
    return ph.rec


# ============================================================= four chips
# DESIGN.md section 9: the D-way learner pmeans per-shard gradient means,
# so its params differ from D=1 only by float reduction order (measured
# ~6e-8 after 3 iterations on CPU host devices; the CPU test holds 1e-5).
# On the TPU an f32 matmul at default precision rounds its inputs to
# bf16 (8 significant bits). After the first step the two runs' params
# differ by reduction order (~1e-7); wherever a weight sits on a bf16
# rounding boundary the next rollout's actions then differ at the 1e-3
# level, and the runs drift apart by up to Adam's step (PPO lr 3e-4):
# 2.8e-4 on four v5e chips. The check therefore runs both learners at
# "highest" matmul precision, where only reduction order separates
# them, as on the CPU, and holds the CPU test's bound. The gap at
# default precision is recorded beside it, unchecked.
LEARNER_TOL = 1e-5


def phase_learner_d4(*, devices: int = 4, num_samplers: int = 4,
                     global_batch: int = 64, horizon: int = 128,
                     iterations: int = 3) -> dict:
    """The data-parallel learner over ``devices`` chips against the
    single-device learner, same seed."""
    ph = Phase("learner_d4")

    def spec(d):
        return ExperimentSpec(
            env="cheetah", algo="ppo", model=WALLE_MLP,
            schedule=Schedule(num_samplers=num_samplers,
                              global_batch=global_batch, horizon=horizon,
                              iterations=iterations, seed=SEED,
                              learner_devices=d))

    one = experiment.run(spec(None))
    many = ph.timed(lambda: experiment.run(spec(devices)))
    ph.placed(many.params)
    # params return to device 0 for the rollout after every step; the
    # optimizer state stays where the learner mesh put it
    mesh = {d for leaf in jax.tree.leaves(many.runner.opt_state)
            for d in leaf.sharding.device_set}
    ph.require(f"learner state spans {devices} distinct chips",
               len(mesh) == devices, tol="exact",
               value=sorted(d.id for d in mesh))
    ph.rec["gap_default_precision"] = _max_abs_diff(one.params, many.params)
    with jax.default_matmul_precision("highest"):
        one = experiment.run(spec(None))
        many = experiment.run(spec(devices))
    gap = _max_abs_diff(one.params, many.params)
    ph.require(f"D={devices} params match D=1 at highest matmul precision",
               gap <= LEARNER_TOL, tol={"atol": LEARNER_TOL}, value=gap)
    ph.warm(lambda: experiment.run(spec(devices), iterations=1))
    return ph.rec


# ===================================================================== main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the D=4 data-parallel learner against "
                         "D=1 (needs four chips)")
    args = ap.parse_args(argv)
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 1
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    compile_cache.enable()
    clock()
    phases = ([phase_learner_d4] if args.four_chips else
              [phase_paper, phase_env_plane, phase_off_policy,
               phase_actors, phase_serve])
    for phase in phases:
        rec = phase()
        print(json.dumps(rec), flush=True)
        if rec["platform"] != "tpu":
            print(f"chip_smoke: phase {rec['phase']} ran on "
                  f"{rec['platform']!r}", file=sys.stderr)
            return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
