"""Rebins (skin-contract and forced) per 1,000 MD steps in the window,
as the engine's ``TrajectoryResult`` counts them."""


def read(run):
    w = run["window"]
    if not w.units or "rebins" not in w.counters:
        return None
    return 1e3 * (w.counters["rebins"] + w.counters["forced_rebins"]) \
        / w.units
