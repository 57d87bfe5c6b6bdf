"""svcbench: the chip benchmark of the SVC engine (see ``run.py``)."""
