"""Device time of the Pallas pair kernel's own events per unit of the
window (an MD step in ``.traj`` cells, a force call in ``.force``), in ms."""


def read(run):
    t = run["trace"]
    if not t or not t["kernel_events"] or not run["window"].units:
        return None
    return 1e3 * t["kernel_s"] / run["window"].units
