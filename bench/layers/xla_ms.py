"""Device busy time outside the Pallas pair kernel's events per unit of
the window (an MD step or a force call), in ms: binning, scatter-back,
the integrator and every other XLA operation."""


def read(run):
    t = run["trace"]
    if not t or not run["window"].units:
        return None
    return 1e3 * t["xla_s"] / run["window"].units
