"""Chip benchmark: the yardstick the cells share (see ../run.py)."""
