"""Device time of the scatter-back (scope ``scatter_back``: slot planes
back to particle order) per unit of the window (a force call or an MD
step), in ms; None where the trace holds no such scope."""

from bench.scopes import per_unit_ms


def read(run):
    return per_unit_ms(run, "scatter_back")
