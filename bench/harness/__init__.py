"""The chip benchmark of the served bi-metric search (see ``bench/run.py``)."""
