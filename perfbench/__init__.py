"""The chip benchmark of the arena search service (see run.py)."""
