"""The on-chip benchmark of the provisioning engine (see ``bench/harness.py``)."""
