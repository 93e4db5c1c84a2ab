"""Batcher fill: mean real rows per dispatch in the window, over max_batch.

Read from the harness's record of each dispatch that the program's
``DynamicBatcher.next_span`` formed.
"""


def read(run):
    fills = run.fills
    if not fills.size:
        return None
    return float(fills.mean()) / run.max_batch
