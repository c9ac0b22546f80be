"""The chip benchmark of the dataframe engine: ``python3 bench/run.py``."""
