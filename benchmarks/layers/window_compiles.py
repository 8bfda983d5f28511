"""Compiler runs inside the measured window (persistent-cache requests
minus hits, from jax.monitoring).  Expected 0: everything the window
uses was warmed in set-up.  Source: program_counter."""


def read(run):
    return run["counters"].get("window_compiler_runs")
