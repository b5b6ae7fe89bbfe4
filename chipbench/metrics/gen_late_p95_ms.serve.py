"""95th percentile of how late the load generator submitted a request
(submit time minus due time), in ms, over the traced window.  The loop
submits only between service steps."""
import numpy as np


def read(ctx):
    late = ctx.get("gen_late_s") or []
    if not late:
        return None
    return float(np.quantile(np.asarray(late), 0.95)) * 1e3
