"""Chip benchmark of the live agentic-RL loop (see ``run.py``)."""
