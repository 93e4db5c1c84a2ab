"""The feature interaction on the device: the mean time per served step in
which some op of the program's named scope ``interact`` ran (the stack of
the bags and the pairwise dots), in ms.

Read from the device trace, each op resolved to its scope through the
compiled step's HLO (``chipbench.scopes``); null when no op
resolves to ``interact``.
"""

from chipbench import scopes


def read(run):
    return scopes.scope_ms(run, "interact")
