"""The on-chip benchmark of the replicated store (see ``run.py``)."""
