"""The on-chip benchmark: ``python3 bench/run.py --workload <name> ...``."""
