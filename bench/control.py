"""Readings that the limits of ``correct`` are set from; the benchmark's
own runs never run this.

    python3 bench/control.py --workload <cell> --first-seed <n> --seeds 12 \
        [--controls 3] [--out <file>]

For each of ``--seeds`` seeds it builds the system as a run of the cell
does, drives its first calls and compares them with the plain reference
(``reflib.compare``): the lower readings. For the first ``--controls``
seeds it also puts the reference itself in the system's place, computed
in bfloat16 (the configuration states float32), and with each fault the
cell can have planted in it: half of each minibatch left out (the mean
taken over the rest). A reference that follows the program follows
whatever stands in the program's place, as a run of the cell does
(``harness.run_reference``). A step that leaves the state unchanged needs
no run: it reads 1 on the gradient and parameter-change numbers by their
definition.

One JSON line per reading; the cell's own size, on the chips it asks for.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def reading(cell, reference, seed: int, observed: dict) -> dict:
    """``observed`` (the program's, or what stands in its place) against
    the reference."""
    from bench import reflib
    return reflib.compare(observed, harness.run_reference(
        reference, cell.config, cell.traffic, seed, observed))


def program_observed(cell, reference, seed: int) -> dict:
    from repro import experiment
    from bench import spec
    cfg, traffic = cell.config, cell.traffic
    runner = experiment.build(spec.experiment_spec(cfg, traffic, seed))
    if "replay_fill" in traffic:
        harness.fill_replay(runner, cfg, traffic, seed)
    caller = harness.Caller(runner, traffic, cfg["loss_key"])
    observed = harness.check_steps(caller, reference, harness.CHECK_STEPS)
    del caller, runner
    gc.collect()
    return observed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    cell = harness.Cell(harness.load_json(ROOT / "BENCHMARK.json"),
                        args.workload, ROOT)
    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < cell.chips:
        print("control: needs the cell's TPU chips", file=sys.stderr)
        return 1
    harness.enable_compile_cache(jax, ROOT)
    cfg, traffic = cell.config, cell.traffic
    reference = cell.reference()
    faults = ["half_batch"]
    out = open(args.out, "a") if args.out else None
    try:
        for i in range(args.seeds):
            seed = args.first_seed + i
            t0 = time.perf_counter()
            stand_ins = {"program": program_observed(cell, reference, seed)}
            if i < args.controls:
                stand_ins["control_bf16"] = reference.run(
                    cfg, traffic, seed, harness.CHECK_STEPS,
                    dtype=jnp.bfloat16, precision="default")
                for fault in faults:
                    stand_ins[f"fault_{fault}"] = reference.run(
                        cfg, traffic, seed, harness.CHECK_STEPS, fault=fault)
            rows = [(kind, reading(cell, reference, seed, observed))
                    for kind, observed in stand_ins.items()]
            for kind, numbers in rows:
                line = json.dumps({"workload": cell.name, "seed": seed,
                                   "kind": kind, **numbers,
                                   "seconds": time.perf_counter() - t0})
                print(line, flush=True)
                if out:
                    out.write(line + "\n")
                    out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
