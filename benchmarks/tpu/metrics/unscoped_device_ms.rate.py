"""The step's device time outside the four named scopes of the program
(``translate``, ``sls``, ``interact``, ``mlp``): the mean time per served
step in which no op of a scope ran, idle time inside the step included, in
ms. The four scopes' readings and this one make up ``step_device_ms.rate``;
a layer that leaves its scope shows here.

Read from the device trace, each op resolved to its scope through the
compiled step's HLO (``chipbench.scopes``); null when no op resolves to
any scope.
"""

from chipbench import scopes


def read(run):
    return scopes.unscoped_ms(run)
