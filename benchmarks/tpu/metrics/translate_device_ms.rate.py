"""Logical-to-stored-rank translation on the device: the mean time per
served step in which some op of the program's named scope ``translate`` ran
(the ``rank_of`` hash-table gather in front of each table's SLS), in ms.

Read from the device trace, each op resolved to its scope through the
compiled step's HLO (``chipbench.scopes``); null when no op
resolves to ``translate``.
"""

from chipbench import scopes


def read(run):
    return scopes.scope_ms(run, "translate")
