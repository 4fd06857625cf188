"""The 90th percentile of the host-clock time of every force call in the
window (each call ends in ``block_until_ready``), in ms."""

import statistics


def read(run):
    times = run["window"].unit_times
    if len(times) < 10:
        return None
    return 1e3 * statistics.quantiles(times, n=10, method="inclusive")[-1]
