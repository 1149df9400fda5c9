"""The chip benchmark: ``python bench/run.py --workload <cell> ...``."""
