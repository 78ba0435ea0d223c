"""Chip benchmark of the partial adaptive index (see ``bench/run.py``)."""
