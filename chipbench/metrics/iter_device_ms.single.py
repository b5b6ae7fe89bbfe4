"""Device busy time per colony iteration in the traced window, in ms."""


def read(ctx):
    s = ctx.get("summary")
    if s is None or not ctx.get("iterations"):
        return None
    return s.busy_s * 1e3 / ctx["iterations"]
