"""Shared code of the benchmark: traffic, statistics, peaks, shape
arithmetic, trace reduction, the plain reference and the output check.

Nothing here imports the program except ``system.py``, the one adapter
that builds the system under test.
"""
