"""Device time of binning (scope ``bin``: cell ids, counts, sort, rank,
the slot planes) per unit of the window (a force call or an MD step), in
ms; None where the trace holds no such scope."""

from bench.scopes import per_unit_ms


def read(run):
    return per_unit_ms(run, "bin")
