"""Chip benchmark of robust training and aggregation (``python3 bench/run.py``).

Everything the benchmark needs lives in this directory and is found by
name from ``BENCHMARK.json``: a configuration is ``configs/<name>.json``,
a traffic mix ``workloads/<name>.json``, a kind of measured window
``windows/<kind>.py`` and a per-layer metric ``layer_metrics/<name>.py``.
"""
