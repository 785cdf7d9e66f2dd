"""The chip benchmark of the FedAT simulator (see ``bench/run.py``)."""
