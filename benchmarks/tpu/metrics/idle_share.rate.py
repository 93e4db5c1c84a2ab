"""Device idle share of the window, in %: 1 - (union of device-op intervals
over the traced window). At a fixed offered rate it includes waiting for
arrivals, so a faster step raises it; read it beside host_gap_ms.
"""

from chipbench import trace


def read(run):
    win = run.window()
    if win is None or not run.trace["ops"]:
        return None
    return 100.0 * (1.0 - trace.busy_ns(run.trace, *win) / (win[1] - win[0]))
