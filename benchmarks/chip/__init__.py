"""On-chip benchmark of the transfer tuner; ``run.py`` is its command."""
