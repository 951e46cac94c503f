"""Per chip, the time in which a collective op runs and no compute op does,
over the traced window, in percent; the worst chip. None where the trace
holds no collective."""

from lib import xplane


def compute(args, run, measured, trace):
    if trace is None:
        return None
    has = any(xplane.COLLECTIVE.search(e[0])
              for ev in trace["events"].values() for e in ev)
    if not has:
        return None
    worst = max(xplane.exposed_collective_seconds(ev)
                for ev in trace["events"].values())
    return 100.0 * worst / trace["window_s"]
