"""The chip benchmark of the AoPI control loop (see run.py)."""
