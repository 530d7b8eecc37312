"""The chip benchmark of the online causal engine (see ``run.py``)."""
