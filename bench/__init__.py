"""Chip benchmark of Dirigent's live serving path (see ``bench/run.py``)."""
