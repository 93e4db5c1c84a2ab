"""Rows the served step computes: the mean, over the traced steps, of the
``k`` of the program's ``rows_<k>`` scope that ran in each step (the row
bucket the step chose on the device for the rows its batch fills), in rows.
A step in which no op of such a scope ran computes every row of its padded
batch, ``max_batch``.

Read from the device trace, each op resolved to its scope through the
compiled step's HLO (``chipbench.scopes``); null when no op resolves to
any of the step's scopes (``scopes.SCOPES``).
"""

import re

import numpy as np
from chipbench import scopes

_BUCKET = re.compile(r"rows_(\d+)")


def read(run):
    found = scopes.split_of(run)
    if found is None or found.unscoped_ns is None:
        return None
    rows = np.zeros(len(run.steps()))
    for segment, ns in found.segment_ns.items():
        if m := _BUCKET.fullmatch(segment):
            rows = np.maximum(rows, np.where(ns > 0, int(m.group(1)), 0))
    return float(np.where(rows > 0, rows, run.max_batch).mean())
