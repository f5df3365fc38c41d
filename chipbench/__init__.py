"""Chip benchmark of the one-round federation (see ``run.py``)."""
