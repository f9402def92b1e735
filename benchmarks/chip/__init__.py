"""The chip benchmark: cells of one model configuration under one traffic
mix, measured on the accelerator and checked against a plain float32
reference.  ``run_cell.py`` is the entry point; ``BENCHMARK.json`` at the
repository root lists the cells and metrics.
"""
