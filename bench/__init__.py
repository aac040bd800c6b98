"""The chip benchmark of the streaming partitioner (see ``bench/harness.py``)."""
