"""The chip benchmark of the graph engine: ``python3 bench/run.py --help``."""
