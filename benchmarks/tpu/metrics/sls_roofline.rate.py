"""The SLS's share of its roofline, in %.

For each step in the window, the least time the chip could take for the
SLS work its real rows require (``chipbench.scopes.sls_least_time_s``:
distinct rows read once over peak HBM bandwidth, or one add per pooled
element over peak FLOP/s, whichever is longer), summed, over the summed
device time of the program's ``sls`` scope in those steps. It reads the
scope, so it holds whatever implements the SLS.
"""

from chipbench import scopes


def read(run):
    sls_ms = scopes.scope_ms(run, "sls")
    if sls_ms is None or run.peaks is None:
        return None
    traced = run.batches[:len(run.steps())]
    least = sum(scopes.sls_least_time_s(run.cfg, run.pool_indices[ids],
                                        run.peaks)
                for ids in traced)
    return 100.0 * least / (sls_ms * 1e-3 * len(traced))
