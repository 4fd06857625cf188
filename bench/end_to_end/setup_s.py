"""Seconds from the start of the process to the start of the window:
imports, state generation, planning, compilation and warm-up."""


def read(run):
    return run["setup_s"]
