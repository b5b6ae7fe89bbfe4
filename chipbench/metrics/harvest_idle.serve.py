"""Device idle share while the host is harvesting finished requests
(``aco.harvest``: reading them back and freeing their slots), as a share
of the resident time (the traced window less the host's ``bench.wait``),
averaged over the chips.  Each span counts by its self time (less its
child ``aco.*`` spans), so the idle shares of the four host phases, of
``aco.step``'s own time and of the time outside every ``aco.*`` span add
up to ``resident_idle.serve``."""
from chipbench.harness import phases


def read(ctx):
    s = ctx.get("summary")
    if s is None:
        return None
    idle = phases.phase_idle(s)
    if idle is None:
        return None
    # a phase that never ran in the window held the chip for no time
    return idle.get("aco.harvest", 0.0)
