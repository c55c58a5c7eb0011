"""The chip benchmark: see run.py and harness.py."""
