"""The chip benchmark of the TPC-H engine: run one cell with ``bench/run.py``."""
