"""Chip benchmark of the LM serving substrate (see ``run.py``)."""
