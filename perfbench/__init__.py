"""The chip benchmark of the GraphEdge served path (see ``run.py``)."""
