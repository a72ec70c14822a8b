"""The chip benchmark: ``BENCHMARK.json`` at the repository root names
its cells; ``bench/run.py`` runs one (``bench/harness.py``)."""
