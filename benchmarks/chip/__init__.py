"""Chip benchmark of the live disaggregated serving path; see run.py."""
