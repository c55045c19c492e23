"""On-chip benchmark of the collaborative MoE serving path (run.py)."""
