"""Benchmark harness of the Q-StaR chip benchmark (see ``bench/run.py``)."""
