"""Slot occupancy of the chunks dispatched in the traced window: the
occupied slots over the slots stepped, summed over the
``aco.chunk_dispatch`` spans (a stepped pool steps every slot, occupied
or not)."""
from chipbench.harness import phases


def read(ctx):
    s = ctx.get("summary")
    return None if s is None else phases.dispatch_fill(s)[0]
