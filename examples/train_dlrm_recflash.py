"""End-to-end driver: train a ~100M-parameter DLRM with the RecFlash layout.

Runs a few hundred steps of CTR training on synthetic Criteo-like data with
the frequency-remapped tables (AF+PD), row-wise adagrad on the tables,
AdamW on the MLPs, and the fault-tolerant TrainLoop (atomic checkpoints +
resume). Identical to:

    PYTHONPATH=src python -m repro.launch.train --steps 300

This is the paper's offline phase + training stage (Fig. 8) end to end.
"""

import sys

from repro.launch.train import main

if __name__ == "__main__":
    sys.argv = [sys.argv[0], "--steps", "300", "--batch", "256",
                "--ckpt-dir", "/tmp/recflash_dlrm_ckpt", *sys.argv[1:]]
    raise SystemExit(main())
