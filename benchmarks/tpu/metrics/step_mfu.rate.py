"""The whole served step's share of the chip's peak, in %.

For each step in the window, the least time the chip could take for the
work its real rows require (the configuration's reference counts it:
``flops_per_sample`` and ``step_bytes``; ``chipbench.yardstick`` takes FLOPs
over peak FLOP/s or distinct-row, weight and I/O bytes over peak HBM
bandwidth, whichever is longer), summed, over the summed device time of
those steps.
"""

from chipbench import config, yardstick


def read(run):
    steps = run.steps()
    if not steps or run.peaks is None:
        return None
    reference = config.reference(run.cfg)
    per_sample = reference.flops_per_sample(run.cfg)
    least = 0.0
    for ids in run.batches[:len(steps)]:
        rows = run.pool_indices[ids]
        least += yardstick.least_time_s(per_sample * ids.size,
                                        reference.step_bytes(run.cfg, rows),
                                        run.peaks)[0]
    device_s = sum(e - s for s, e in steps) * 1e-9
    return 100.0 * least / device_s
