"""The benchmark of the AQP engine on the chip: see ``bench/run.py``."""
