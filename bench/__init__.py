"""Bullion's on-chip benchmark: ``python3 bench/run.py --workload <name>
--seed <n> --seconds <s> --trace <0|1>``. See ``bench/run.py``."""
