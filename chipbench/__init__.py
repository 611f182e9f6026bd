"""The on-chip benchmark of the FatPaths simulator: ``python3 -m
chipbench.run``; see ``chipbench/run.py``."""
