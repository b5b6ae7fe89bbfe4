"""Device idle share over the parts of the traced window in which some
request was in the system (the window less the host's ``bench.wait``
spans), averaged over the chips: gaps the host holds the chip in, not
gaps with nothing to do."""


def read(ctx):
    s = ctx.get("summary")
    if s is None or not s.busy:
        return None
    return s.idle_share_within(s.resident_intervals())
