"""Device busy time summed over the chips, per request completed in the
traced window, in ms."""


def read(ctx):
    s = ctx.get("summary")
    if s is None or not ctx.get("completed"):
        return None
    return sum(s.busy_s_per_device().values()) * 1e3 / ctx["completed"]
