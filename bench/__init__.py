"""Chip benchmark of ComPar's train and serve paths; see run.py."""
