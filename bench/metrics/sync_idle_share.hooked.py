"""sync_idle_share.hooked: % of the window in which the most idle device
is idle while the host waits in ``engine.sync`` for the window's outputs:
idle time the runtime adds between the device finishing and the host
going on.  Nothing where the program opens no spans."""
import hostspans


def read(run):
    return hostspans.idle_share(run, hostspans.SYNC)
