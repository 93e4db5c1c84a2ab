"""Host dispatch time: the mean device-idle gap before each served step whose
batch was already waiting when the previous step's logits came back.

Such a gap is host time (readback, batching, assembly, transfer, launch),
not an empty queue. Read from the device trace, in ms.
"""


def read(run):
    steps = run.steps()
    if steps is None:
        return None
    gaps = [steps[k][0] - steps[k - 1][1] for k in range(1, len(steps))
            if run.waiting[k]]
    if not gaps:
        return None
    return sum(gaps) / len(gaps) * 1e-6
