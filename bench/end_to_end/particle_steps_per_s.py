"""Particles x MD steps completed in the window, over the time from the
window's start to the end of its last trajectory chunk."""


def read(run):
    w = run["window"]
    return run["n"] * w.units / w.seconds if w.units else None
