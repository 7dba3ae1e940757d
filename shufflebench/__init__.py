"""The shuffle benchmark: NPB IS range-sort jobs through TpuShuffleManager.

``run.py`` is the command ``BENCHMARK.json`` names. Everything that
belongs to one deployment, one traffic mix or one per-layer metric is a
file of its own (``configs/``, ``traffic/``, ``metrics/``), found by the
name ``BENCHMARK.json`` gives it.
"""
