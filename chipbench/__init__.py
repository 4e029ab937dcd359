"""Chip benchmark of the FusionLLM training paths (see run.py)."""
