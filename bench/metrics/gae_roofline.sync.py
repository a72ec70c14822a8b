"""``gae_roofline`` in the cells that report ``env_steps_per_s.sync``: the
same reading (``metrics/gae_roofline.py``), kept apart because those cells' rate
is bounded apart."""
import pathlib

from bench import harness

read = harness.load_module(pathlib.Path(__file__).with_name("gae_roofline.py")).read
