"""Padding fill of the chunks dispatched in the traced window: the real
cities over the cities stepped in occupied slots (each padded to its
bucket), summed over the ``aco.chunk_dispatch`` spans."""
from chipbench.harness import phases


def read(ctx):
    s = ctx.get("summary")
    return None if s is None else phases.dispatch_fill(s)[1]
