"""The SLS (pooled embedding lookup) on the device: the mean time per
served step in which some op of the program's named scope ``sls`` ran (each
table's gather and sum, and the relayout copies of the tables that feed the
gather), in ms.

Read from the device trace, each op resolved to its scope through the
compiled step's HLO (``chipbench.scopes``); null when no op
resolves to ``sls``.
"""

from chipbench import scopes


def read(run):
    return scopes.scope_ms(run, "sls")
