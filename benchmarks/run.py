"""Benchmark driver — one section per paper table/figure, with a
machine-readable record of every run.

Prints ``name,us_per_call,derived`` CSV rows and, at the end, writes
``BENCH_<rev>.json`` (per-benchmark throughput + config + timestamp)
into ``--out-dir`` so the perf trajectory is recorded across PRs instead
of evaporating into stdout. Sections:

  fig         fig3..fig7 return/rollout/speedup curves   (paper Figs 3-7)
  fused       fused-engine dispatch-overhead savings
  replay      experience-plane adds/sec + samples/sec per buffer kind
              (including kernel-plane ref/pallas rows for prioritized)
  sampler     actor-plane scaling: samples/sec vs N per backend
              (inline vs threaded vs true worker processes), plus the
              vector-collection row at env_batch=B     [DESIGN.md §6]
  learner     learner-plane scaling: train-step time + samples/sec vs
              D devices (sharded learner, forced host devices)
                                                       [DESIGN.md §9]
  env_step    env-plane: fused step+auto-reset kernels ref-vs-pallas at
              B in {1k,10k,100k} + VectorEnv rollout throughput vs the
              inline N=1 baseline                      [DESIGN.md §7]
  serving     serving plane: PolicyServer p50/p99 latency + requests/sec
              vs batch-window deadline, and the hot-swap pickup latency
                                                       [DESIGN.md §8]
  kernels_lm  attn_* / selective_scan_* / decode_step_* sampler benches
  kernels_rl  gae / sum_tree / replay_ring ref-vs-pallas  [DESIGN.md §5]
  roofline    three-term roofline per (arch x shape x mesh)

The roofline section reads results/dryrun/*.json produced by
``python -m repro.launch.dryrun --all --both-meshes`` (run it first; rows
are skipped gracefully if absent).

  python -m benchmarks.run                          # everything
  python -m benchmarks.run --sections kernels_rl    # one section, fast
  python -m benchmarks.run --compare OLD.json NEW.json
                                                    # diff two reports;
                                                    # exit 1 on regression

``--compare`` diffs the rows two BENCH files share and prints per-metric
deltas; throughput metrics (``*_per_sec``) that drop — or ``us_per_call``
that rises — by more than ``--threshold`` percent count as regressions
and make the exit status nonzero, so CI can consume the BENCH trajectory
directly.
"""
from __future__ import annotations

import argparse
import datetime
import json
import os
import platform
import subprocess
import sys
import time


def _sections():
    from benchmarks import env_step_bench, fault_bench, fig_parallel, \
        fused_vs_stepped, kernel_bench, learner_scaling, replay_bench, \
        roofline, sampler_scaling, serving_bench
    return {
        "fig": fig_parallel.run_all,
        "fused": fused_vs_stepped.run_all,
        "replay": replay_bench.run_all,
        "sampler": sampler_scaling.run_all,
        "learner": learner_scaling.run_all,
        "env_step": env_step_bench.run_all,
        "serving": serving_bench.run_all,
        "fault": fault_bench.run_all,
        "kernels_lm": kernel_bench.run_lm,
        "kernels_rl": kernel_bench.run_rl,
        "roofline": roofline.main,
    }


def _git_rev() -> str:
    """Short HEAD rev, ``-dirty``-suffixed when the tree has uncommitted
    changes — numbers from unfinished work must not be attributed to the
    last commit in the recorded trajectory."""
    cwd = os.path.dirname(os.path.abspath(__file__))
    try:
        rev = subprocess.check_output(
            ["git", "rev-parse", "--short", "HEAD"], cwd=cwd,
            stderr=subprocess.DEVNULL).decode().strip()
        dirty = subprocess.call(
            ["git", "diff-index", "--quiet", "HEAD"], cwd=cwd,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL) != 0
        untracked = subprocess.check_output(
            ["git", "ls-files", "--others", "--exclude-standard"],
            cwd=cwd, stderr=subprocess.DEVNULL).strip()
        return rev + ("-dirty" if dirty or untracked else "")
    except (OSError, subprocess.CalledProcessError):
        return "unknown"


def check_dirty_overwrite(out_dir: str, rev: str, force: bool) -> None:
    """Refuse to land a ``-dirty`` report next to its clean-rev sibling.

    A dirty tree's numbers describe unfinished work; writing
    ``BENCH_<rev>-dirty.json`` beside the committed ``BENCH_<rev>.json``
    invites comparing (or worse, shipping) them as if they were the
    recorded trajectory. ``--force`` overrides for local iteration.
    """
    if force or not rev.endswith("-dirty"):
        return
    clean = os.path.join(out_dir, f"BENCH_{rev[:-len('-dirty')]}.json")
    if os.path.exists(clean):
        sys.exit(
            f"error: the tree is dirty but {clean} already records this "
            f"rev from a clean tree; commit your changes (so the report "
            f"lands under the new rev) or pass --force to write "
            f"BENCH_{rev}.json anyway")


def write_report(out_dir: str, sections, force: bool = False) -> str:
    """Serialize every emitted row (benchmarks.common.RECORDS) plus the
    run's config into ``<out_dir>/BENCH_<rev>.json``; returns the path."""
    import jax

    from benchmarks import common
    check_dirty_overwrite(out_dir, _git_rev(), force)
    payload = {
        "rev": _git_rev(),
        "timestamp": datetime.datetime.now(
            datetime.timezone.utc).isoformat(),
        "unix_time": time.time(),
        "config": {
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
            "python": platform.python_version(),
            "platform": platform.platform(),
            "sections": list(sections),
        },
        "benchmarks": common.RECORDS,
    }
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"BENCH_{payload['rev']}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    return path


def _load_records(path: str) -> dict:
    with open(path) as f:
        payload = json.load(f)
    # index by row name; a re-emitted name keeps its latest measurement
    return {r["name"]: r for r in payload.get("benchmarks", [])}, \
        payload.get("rev", "?")


def compare(old_path: str, new_path: str, threshold: float) -> int:
    """Diff the benchmark rows two BENCH reports share.

    Prints one line per (row, metric) with old/new values and the percent
    delta. ``us_per_call`` and latency metrics (``*_ms`` — e.g. the fault
    section's ``recovery_ms``) are lower-is-better; ``*_per_sec`` metrics
    are higher-is-better; everything else is informational. Returns the
    number of metrics that regressed by more than ``threshold`` percent.
    """
    old, old_rev = _load_records(old_path)
    new, new_rev = _load_records(new_path)
    for side, rev in (("old", old_rev), ("new", new_rev)):
        if rev.endswith("-dirty"):
            print(f"# WARNING: {side} report {rev} was produced from a "
                  f"dirty tree — its numbers may not match any commit",
                  file=sys.stderr)
    shared = [n for n in new if n in old]
    only_old = sorted(set(old) - set(new))
    only_new = sorted(set(new) - set(old))
    print(f"# compare {old_rev} -> {new_rev}: {len(shared)} shared rows, "
          f"{len(only_old)} dropped, {len(only_new)} added")
    print("name,metric,old,new,delta_pct,verdict")
    regressions = 0
    for name in shared:
        pairs = [("us_per_call", old[name]["us_per_call"],
                  new[name]["us_per_call"], False)]
        om, nm = old[name].get("metrics", {}), new[name].get("metrics", {})
        for k in sorted(set(om) & set(nm)):
            pairs.append((k, om[k], nm[k], k.endswith("per_sec")))
        for metric, o, n, higher_better in pairs:
            if not o:
                continue
            delta = (n - o) / abs(o) * 100.0
            lower_better = (metric == "us_per_call"
                            or metric.endswith("_ms"))
            judged = higher_better or lower_better
            regressed = judged and (
                -delta > threshold if higher_better else delta > threshold)
            verdict = ("REGRESSED" if regressed
                       else "ok" if judged else "info")
            regressions += regressed
            print(f"{name},{metric},{o:.6g},{n:.6g},{delta:+.1f},{verdict}")
    if regressions:
        print(f"# {regressions} metric(s) regressed more than "
              f"{threshold:.0f}%")
    return regressions


def main(argv=None) -> None:
    table = _sections()
    ap = argparse.ArgumentParser()
    ap.add_argument("--sections", default=",".join(table),
                    help="comma-separated subset of: " + ", ".join(table))
    ap.add_argument("--out-dir", default="results",
                    help="where BENCH_<rev>.json lands (default: results)")
    ap.add_argument("--compare", nargs=2, metavar=("OLD.json", "NEW.json"),
                    default=None,
                    help="diff two BENCH reports instead of running "
                         "benchmarks; nonzero exit on regression")
    ap.add_argument("--threshold", type=float, default=10.0,
                    help="--compare: percent drop in *_per_sec (or rise "
                         "in us_per_call) that counts as a regression "
                         "(default 10)")
    ap.add_argument("--force", action="store_true",
                    help="write BENCH_<rev>-dirty.json even when the "
                         "clean-tree BENCH_<rev>.json already exists "
                         "(local iteration only — dirty reports are not "
                         "part of the recorded trajectory)")
    args = ap.parse_args(argv)
    if args.compare is not None:
        sys.exit(1 if compare(args.compare[0], args.compare[1],
                              args.threshold) else 0)
    names = [s.strip() for s in args.sections.split(",") if s.strip()]
    unknown = [s for s in names if s not in table]
    if unknown:
        ap.error(f"unknown sections {unknown}; choose from {list(table)}")
    # fail before benchmarks run, not after minutes of measurement
    check_dirty_overwrite(args.out_dir, _git_rev(), args.force)
    from repro.launch import compile_cache
    compile_cache.enable()

    print("name,us_per_call,derived")
    for name in names:
        table[name]()
    path = write_report(args.out_dir, names, force=args.force)
    print(f"# wrote {path}")


if __name__ == "__main__":
    main()
