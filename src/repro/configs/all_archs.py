"""Import side-effect module: populates the arch registry.

Covers GNN (graphsage), RecSys (din/dlrm/bert4rec) and the paper's own
benchmark models (rmc).
"""

import repro.configs.bert4rec_arch     # noqa: F401
import repro.configs.din_arch          # noqa: F401
import repro.configs.dlrm_mlperf       # noqa: F401
import repro.configs.dlrm_rm2          # noqa: F401
import repro.configs.graphsage_reddit  # noqa: F401
import repro.configs.rmc               # noqa: F401
