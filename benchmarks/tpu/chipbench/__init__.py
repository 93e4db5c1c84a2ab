"""Chip benchmark of the served DLRM path (see ``harness.py``)."""
