"""Device time of the colony step's ``deposit`` scope (the busy union of
the operations the program's ``jax.named_scope("deposit")`` lowered to,
read from its compiled modules) per colony iteration in the traced
window, in ms."""
from chipbench.harness import phases


def read(ctx):
    s, scopes = ctx.get("summary"), ctx.get("scopes")
    if s is None or scopes is None or not ctx.get("iterations"):
        return None
    busy = phases.scope_busy_s(s, scopes).get("deposit")
    return None if busy is None else busy * 1e3 / ctx["iterations"]
