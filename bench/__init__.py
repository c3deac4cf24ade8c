"""The chip benchmark of the GoldDiff serving path (see ``bench/run.py``)."""
