"""On-chip benchmark of the MPSL train step: `python3 chipbench/run.py`."""
