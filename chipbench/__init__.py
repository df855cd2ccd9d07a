"""A chip benchmark of the served sparse networks: see run.py."""
