"""On-chip benchmark of the frequent-itemset miners: see ``run.py``."""
