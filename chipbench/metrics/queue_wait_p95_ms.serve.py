"""95th percentile of queue wait (submit to admission into a slot, the
``wait_s`` of the service's ``admit`` events), in ms, over the requests
submitted in the traced window."""
import numpy as np


def read(ctx):
    waits = ctx.get("queue_wait_s") or []
    if not waits:
        return None
    return float(np.quantile(np.asarray(waits), 0.95)) * 1e3
