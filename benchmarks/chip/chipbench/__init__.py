"""Chip benchmark of the tensorized CloudSim: one cell, one run.

``benchmarks/chip/run.py`` is the entry point.  Deployments, traffic
mixes, deployment kinds and metric readers are files found by the names
in ``BENCHMARK.json`` (``configs/``, ``traffic/``, ``deployments/``,
``metrics/``); nothing in this package knows one kind of deployment.
"""
