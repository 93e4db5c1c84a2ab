"""The MLPs on the device: the mean time per served step in which some op of
the program's named scope ``mlp`` ran (the bottom MLP, the top MLP and the
logit slice), in ms.

Read from the device trace, each op resolved to its scope through the
compiled step's HLO (``chipbench.scopes``); null when no op
resolves to ``mlp``.
"""

from chipbench import scopes


def read(run):
    return scopes.scope_ms(run, "mlp")
