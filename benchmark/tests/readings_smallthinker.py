"""`readings.py` for the SmallThinker cell: the same readings, many seeds
in one process, with this cell's faults (`faults_smallthinker.py`) to
plant:

    python -m benchmark.tests.readings_smallthinker \
        --workload smallthinker_fit --seeds 1,2,3 [--control] \
        [--fault window_dropped] [--out file.jsonl]
"""
import sys

from benchmark.tests import faults_smallthinker, readings

readings.faults = faults_smallthinker

if __name__ == "__main__":
    sys.exit(readings.main())
