"""Chip benchmark of federated GPDMM rounds (see ``run.py``)."""
