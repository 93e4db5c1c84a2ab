"""Device idle share of the window, in %: 1 - (union of device-op intervals
over the traced window). At a fixed offered rate it includes waiting for
arrivals, so a faster step raises it; read it beside host_gap_ms.
"""


def read(run):
    busy = run.busy()
    if busy is None or not run.trace["ops"]:
        return None
    return 100.0 * (1.0 - busy.busy_ns / (busy.hi - busy.lo))
