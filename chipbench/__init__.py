"""On-chip benchmark of the codistillation trainer: see ``run.py``."""
