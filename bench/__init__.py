"""Chip benchmark of the FedOptima pod round (see ``bench/run.py``)."""
