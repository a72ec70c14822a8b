"""The benchmark's cells cut to a size the CPU test run holds: the same
files, configurations and code paths, smaller batches and horizons."""
from __future__ import annotations

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402


def tiny_cell(name: str, root: pathlib.Path = ROOT) -> harness.Cell:
    cell = harness.Cell(harness.load_json(root / "BENCHMARK.json"), name,
                        root)
    t = cell.traffic
    if t.get("env_batch"):
        t["env_batch"] = 8 if "replay_fill" in t else 32
    if t.get("horizon", 0) > 16:
        t["horizon"] = 20
    if t.get("chunk"):
        t["chunk"] = min(int(t["chunk"]), 2)
    if "replay_fill" in t:
        t["replay_fill"] = {"envs": 32, "steps": 32}
        t["updates_per_collect"] = 8
        cell.config["buffer_kwargs"]["capacity"] = 1024
    return cell


def run(cell: harness.Cell, seed: int = 2 ** 31 + 7) -> dict:
    """A whole run of the cell on the CPU, with the chip check skipped."""
    import time
    return harness.run_cell(cell, seed, 0.5, False,
                            t_start=time.perf_counter(), require_tpu=False)
