"""The 99th percentile of request latency, from each request's scheduled
arrival to its logit on the host, over every request served in the window.

A tail of the queue behind the batcher: at a fixed rate below capacity it
rests on the few hundred slowest requests, so a host that stands still for
a tenth of a second moves it by tens of milliseconds. It is a per-layer
reading for that reason, beside the median that is judged end to end.
"""

import numpy as np


def read(run):
    served = run.latency_ms[np.isfinite(run.latency_ms)]
    if not served.size:
        return None
    return float(np.percentile(served, 99))
