"""Model step on the device: the mean duration of the served step's program
executions (``jit_serve_step``) in the window, from the device trace, in ms.
"""


def read(run):
    steps = run.steps()
    if not steps:
        return None
    return sum(e - s for s, e in steps) / len(steps) * 1e-6
