"""The chip benchmark: one cell, one run, one JSON line (README.md)."""
