"""Chip benchmark of the served path: one (configuration, traffic) cell per
run, driven through ``DecodeRuntime`` on a TPU. See ``run_cell.py``."""
