"""host_idle_share.hooked: % of the window in which the most idle device
is idle while the engine's serial host path runs, that is while the
innermost open program span is ``layout.to_padded``, ``engine.dispatch``,
``layout.from_padded`` or ``engine.hook``: idle time a fused per-step
program would take away.  Nothing where the program opens no spans."""
import hostspans


def read(run):
    return hostspans.idle_share(run, hostspans.HOST_PATH)
