"""The benchmark: the yardstick later PRs are measured with and cannot edit.

``run.py`` is the command ``BENCHMARK.json`` names. Everything that belongs
to one configuration, one traffic mix or one per-layer metric is a file of
its own, found by the name ``BENCHMARK.json`` gives it (``loader.py``).
"""
