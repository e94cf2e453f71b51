"""Chip benchmark of the ACAR serving path (``python3 bench/run.py``)."""
