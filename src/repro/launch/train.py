"""End-to-end training driver.

Two modes, selected by --mode:
* ``rl``  — the paper's experiment through the unified experiment API:
  any registered algo (ppo/trpo/ddpg/sac) + N parallel samplers on a
  pure-JAX env, on any backend/runtime, with any experience buffer
  (``--buffer {fifo,uniform,prioritized}``). The CLI only builds an
  ``ExperimentSpec`` and delegates to ``repro.experiment.run``;
  CPU-runnable.
* ``lm``  — sequence-model PPO (RLHF-style): synthetic rollout batches
  drive ``make_lm_train_step`` under a mesh, with checkpointing. On CPU use
  a reduced arch (``--arch <id>-reduced``); full configs belong to the
  dry-run.

Checkpoints record the fully-resolved spec in their metadata, so a run is
reproducible from the checkpoint directory alone.

Usage:
  PYTHONPATH=src python -m repro.launch.train --mode rl --env pendulum \
      --algo {ppo,trpo,ddpg,sac} --num-samplers 4 --iterations 20 \
      --backend {inline,threaded,sharded,process,fused} \
      [--num-workers 4]            # process backend: worker-process count \
      [--env-batch 1024]           # env plane: B-instance VectorEnv batch \
      [--buffer prioritized --replay-capacity 100000 --n-step 3] \
      [--kernels {ref,pallas,auto}]   # kernel plane (DESIGN.md §5) \
      [--inject-faults kill:0.2]   # chaos: process workers die on a \
      [--max-respawns 8]           # seeded schedule and are respawned \
      [--min-workers 2 --max-workers 8]  # async elastic fleet (§10) \
      [--staleness decay]          # async staleness-corrected learning
  PYTHONPATH=src python -m repro.launch.train --mode lm \
      --arch mixtral-8x7b-reduced --steps 5
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro import experiment
from repro.algos.ppo import PPOConfig, make_lm_train_step
from repro.checkpoint import save
from repro.configs import get_config
from repro.experiment import ExperimentSpec, Schedule
from repro.launch import compile_cache
from repro.models import transformer
from repro.optim import adam


def spec_from_args(args) -> ExperimentSpec:
    """Resolve the CLI flags into a declarative ExperimentSpec.

    ``--backend fused`` and ``--async`` select runtimes rather than
    sampler backends; the spec keeps the distinction explicit.
    """
    runtime = ("async" if args.async_mode
               else "fused" if args.backend == "fused" else "sync")
    # normalize backend to what the runtime actually does, so checkpoint
    # metadata never records a collection schedule that didn't run: fused
    # has no host-visible backend; async samples with free-running threads
    # unless process workers were requested explicitly
    backend = ("inline" if args.backend == "fused"
               else "threaded" if args.async_mode
               and args.backend != "process" else args.backend)
    # only forward --lr when the user set it, so each algorithm's own
    # learning-rate defaults (ppo 3e-4, trpo vf 1e-3, ddpg 1e-3) apply
    algo_kwargs = {} if args.lr is None else {"lr": args.lr}
    # same for the buffer: only overrides the user set reach the spec, so
    # each buffer kind's own defaults apply and ckpt metadata stays honest
    buffer_kwargs = {k: v for k, v in [
        ("capacity", args.replay_capacity),
        ("batch_size", args.replay_batch),
        ("n_step", args.n_step),
    ] if v is not None}
    staleness = None
    if args.staleness and args.staleness != "off":
        staleness = {"mode": args.staleness}
        if args.staleness_decay is not None:
            staleness["decay"] = args.staleness_decay
    return ExperimentSpec(
        env=args.env,
        algo=args.algo,
        backend=backend,
        runtime=runtime,
        buffer=args.buffer,
        kernels=args.kernels,
        model={"hidden": args.hidden},
        algo_kwargs=algo_kwargs,
        buffer_kwargs=buffer_kwargs,
        staleness=staleness,
        faults=args.inject_faults,
        schedule=Schedule(
            num_samplers=args.num_samplers,
            global_batch=args.global_batch,
            horizon=args.horizon,
            iterations=args.iterations,
            seed=args.seed,
            chunk=args.chunk,
            num_workers=args.num_workers,
            env_batch=args.env_batch,
            learner_devices=args.learner_devices,
            learner_microbatches=args.learner_microbatches,
            fsdp=args.fsdp,
            overlap=args.overlap,
            learner_pods=args.learner_pods,
            max_respawns=args.max_respawns,
            min_workers=args.min_workers,
            max_workers=args.max_workers,
        ),
    )


def run_rl(args) -> None:
    spec = spec_from_args(args)
    result = experiment.run(spec)
    for log in result.logs:
        print(json.dumps(log.as_dict()))
    if args.ckpt_dir:
        save(args.ckpt_dir, args.iterations, result.params,
             metadata={"mode": "rl", "spec": spec.to_dict()})


def run_lm(args) -> None:
    cfg = get_config(args.arch)
    lr = args.lr if args.lr is not None else 3e-4
    key = jax.random.PRNGKey(args.seed)
    params = transformer.init_params(cfg, key)
    opt = adam(lr, moment_dtype=cfg.dtype)
    opt_state = opt.init(params)
    step = jax.jit(make_lm_train_step(cfg, opt, PPOConfig(lr=lr)))
    B, S = args.batch, args.seq_len
    kd = jax.random.PRNGKey(args.seed + 1)
    for i in range(args.steps):
        kd, kb = jax.random.split(kd)
        batch = {
            "tokens": jax.random.randint(kb, (B, S), 0, cfg.vocab_size),
            "targets": jax.random.randint(kb, (B, S), 0, cfg.vocab_size),
            "behavior_logp": -jnp.ones((B, S)) * 5.0,
            "advantages": jax.random.normal(kb, (B, S)),
            "returns": jax.random.normal(kb, (B, S)),
            "mask": jnp.ones((B, S)),
        }
        if cfg.frontend_embeds:
            batch["extra_embeds"] = jnp.zeros(
                (B, cfg.frontend_embeds, cfg.d_model),
                jnp.dtype(cfg.dtype))
        t0 = time.perf_counter()
        params, opt_state, metrics = step(params, opt_state, batch)
        jax.block_until_ready(metrics["loss"])
        print(f"step {i}: loss={float(metrics['loss']):.4f} "
              f"({time.perf_counter() - t0:.2f}s)")
    if args.ckpt_dir:
        save(args.ckpt_dir, args.steps, params,
             metadata={"mode": "lm", "arch": args.arch, "seed": args.seed,
                       "lr": lr, "steps": args.steps,
                       "batch": args.batch, "seq_len": args.seq_len})


def main() -> None:
    ap = argparse.ArgumentParser()
    from repro import registry
    ap.add_argument("--mode", choices=("rl", "lm"), default="rl")
    ap.add_argument("--env", default="pendulum",
                    choices=registry.choices("env"))
    ap.add_argument("--algo", default="ppo",
                    choices=registry.choices("algo"))
    ap.add_argument("--arch", default="mixtral-8x7b-reduced")
    ap.add_argument("--num-samplers", type=int, default=4)
    ap.add_argument("--num-workers", type=int, default=None,
                    help="process backend: rollout worker-process count "
                         "(default: --num-samplers; worker i reuses "
                         "sampler i's seed, so process == inline exactly)")
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--env-batch", type=int, default=None,
                    help="env plane: collect with one device-resident "
                         "VectorEnv of B instances (one batched state "
                         "pytree, fused step+auto-reset) instead of the "
                         "num-samplers × global-batch split; combine "
                         "with --backend fused --kernels pallas for "
                         "single-dispatch iterations (DESIGN.md §7)")
    ap.add_argument("--horizon", type=int, default=128)
    ap.add_argument("--iterations", type=int, default=10)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--lr", type=float, default=None,
                    help="learning rate (default: the algorithm's own; "
                         "lm mode: 3e-4)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default="inline",
                    choices=registry.choices("backend") + ("fused",))
    from repro.kernels.select import MODES as KERNEL_MODES
    ap.add_argument("--kernels", default="auto",
                    choices=KERNEL_MODES,
                    help="kernel-plane implementation for the RL hot "
                         "loop (gae/sum_tree/replay_ring/env_step): "
                         "'ref' pure-JAX oracles (bitwise baseline), "
                         "'pallas' the fused kernels (interpret mode "
                         "off-accelerator), 'auto' compiled pallas on "
                         "TPU/GPU else ref")
    ap.add_argument("--buffer", default=None,
                    choices=registry.choices("buffer"),
                    help="experience buffer kind (default: the "
                         "algorithm's own — fifo on-policy, uniform "
                         "off-policy)")
    ap.add_argument("--replay-capacity", type=int, default=None,
                    help="off-policy buffers: ring capacity")
    ap.add_argument("--replay-batch", type=int, default=None,
                    help="off-policy buffers: learner minibatch size")
    ap.add_argument("--n-step", type=int, default=None,
                    help="off-policy buffers: n-step return horizon")
    ap.add_argument("--learner-devices", type=int, default=None,
                    help="shard the train step data-parallel over D "
                         "devices (shard_map; 1/unset = the historical "
                         "single-device path, bitwise unchanged)")
    ap.add_argument("--learner-microbatches", type=int, default=1,
                    help="gradient-accumulation slices per (per-shard) "
                         "learner batch")
    ap.add_argument("--fsdp", action="store_true",
                    help="shard params and Adam moments across the "
                         "learner mesh per the _param_spec layout rules "
                         "(per-layer all-gather + reduce-scattered grads; "
                         "requires --learner-devices > 1 — DESIGN.md §11)")
    ap.add_argument("--overlap", action="store_true",
                    help="double-buffered pipeline: dispatch iteration "
                         "k's learn and run iteration k+1's collect "
                         "while it executes (sync/fused runtimes; "
                         "IterationLog.overlap_saved_s reports the "
                         "hidden learn time)")
    ap.add_argument("--learner-pods", type=int, default=1,
                    help="split the learner shards over a (pod, data, "
                         "model) mesh — the multi-pod production axis "
                         "names, so the same step lowers across the DCN "
                         "boundary (must divide --learner-devices)")
    ap.add_argument("--chunk", type=int, default=None,
                    help="fused backend: iterations per device dispatch "
                         "(default: all of --iterations in one chunk)")
    ap.add_argument("--async", dest="async_mode", action="store_true")
    ap.add_argument("--inject-faults", default=None, metavar="SPEC",
                    help="fault-injection schedule for process workers, "
                         "e.g. 'kill:0.2,torn:0.05,delay:0.1:80' — "
                         "per-step probabilities of SIGKILL / die-mid-"
                         "write / hang / delay, deterministic per "
                         "(seed, worker, incarnation, step); requires "
                         "--backend process (DESIGN.md §10)")
    ap.add_argument("--max-respawns", type=int, default=3,
                    help="process backend: consecutive-failure budget "
                         "per worker before the run fails (0 disables "
                         "supervised respawn entirely)")
    ap.add_argument("--min-workers", type=int, default=None,
                    help="async process: elastic fleet floor — with "
                         "--max-workers, enables utilization-band "
                         "autoscaling between iterations")
    ap.add_argument("--max-workers", type=int, default=None,
                    help="async process: elastic fleet ceiling (the "
                         "pool pre-provisions ring slots and WorkerSpecs "
                         "up to this count; growth never reallocates)")
    from repro.algos.staleness import MODES as STALENESS_MODES
    ap.add_argument("--staleness", default="off",
                    choices=STALENESS_MODES,
                    help="async staleness correction: 'decay' weights "
                         "samples by decay**version_gap; 'vtrace' also "
                         "applies the truncated importance ratio "
                         "min(rho_clip, pi_now/pi_behavior); 'off' is "
                         "the historical bitwise path")
    ap.add_argument("--staleness-decay", type=float, default=None,
                    help="per-version-gap decay factor (default 0.9)")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    compile_cache.enable()
    (run_rl if args.mode == "rl" else run_lm)(args)


if __name__ == "__main__":
    main()
