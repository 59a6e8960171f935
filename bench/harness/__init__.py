"""The benchmark harness: cells are found by name, run, traced and checked.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own under ``bench/configs``,
``bench/traffic`` and ``bench/metrics``; the modules here read those files
by the names that ``BENCHMARK.json`` gives.
"""
