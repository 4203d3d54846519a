"""Chip benchmark of the compiled QONNX serving path (see ``run.py``)."""
