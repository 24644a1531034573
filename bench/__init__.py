"""On-chip benchmark of the user-centric federated round (see run.py)."""
