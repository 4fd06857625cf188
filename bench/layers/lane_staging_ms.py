"""Device time of the pair kernel's lane staging (the XLA ops under scope
``pair`` that are not kernel events: transposes and pads around the
``pallas_call``) per unit of the window (a force call or an MD step), in
ms; None where the trace holds no such scope."""

from bench.scopes import per_unit_ms


def read(run):
    return per_unit_ms(run, "pair_staging")
