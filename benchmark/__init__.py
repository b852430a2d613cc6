"""The repository's benchmark: `python3 benchmark/run.py --workload <cell> ...`.

Everything a cell is measured with lives in this directory (see README.md);
from the program it takes only the system under test, its spans and counters.
"""
