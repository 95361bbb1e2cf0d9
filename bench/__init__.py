"""The chip benchmark of the served ANN search path (see ``bench/run.py``)."""
