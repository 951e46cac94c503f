"""A percentile of the gaps between consecutive tokens of one request, over
every request whose last token fell in the untraced part of the window.
The stamps are the program's (`ServeFuture.token_times`, on the clock of
the driver's snapshots); None where the futures carry none.
args: {"percentile": 95, "scale": 1000.0}."""

import numpy as np


def gaps(token_times_of_requests, t_from, t_to):
    """All inter-token gaps of the requests that ended in [t_from, t_to)."""
    out = []
    for times in token_times_of_requests:
        if len(times) > 1 and t_from <= times[-1] < t_to:
            out.extend(np.diff(np.asarray(times, np.float64)))
    return out


def compute(args, run, measured, trace):
    stamped = [list(getattr(rec["handle"], "token_times", None) or ())
               for rec in measured.get("records", ())
               if rec.get("done_at") is not None]
    values = gaps(stamped, measured["snap_start"]["t"],
                  measured["snap_end"]["t"])
    if not values:
        return None
    return float(np.percentile(values, float(args["percentile"]))) \
        * float(args.get("scale", 1.0))
