"""Share of the traced window in which no operation ran on the chip."""


def read(ctx):
    s = ctx.get("summary")
    if s is None or not s.busy:
        return None
    return s.idle_share
