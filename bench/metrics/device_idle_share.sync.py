"""``device_idle_share`` in the cells that report ``env_steps_per_s.sync``: the
same reading (``metrics/device_idle_share.py``), kept apart because those cells' rate
is bounded apart."""
import pathlib

from bench import harness

read = harness.load_module(pathlib.Path(__file__).with_name("device_idle_share.py")).read
