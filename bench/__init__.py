"""The benchmark of the streaming SVD service: ``python3 bench/run.py``."""
