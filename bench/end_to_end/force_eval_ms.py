"""The window's time over the force calls it completed, in ms."""


def read(run):
    w = run["window"]
    return 1e3 * w.seconds / w.units if w.units else None
