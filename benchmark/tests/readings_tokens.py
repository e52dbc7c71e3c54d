"""`readings.py` for a token cell: the same readings, many seeds in one
process, with the token cells' faults (`faults_tokens.py`) to plant:

    python -m benchmark.tests.readings_tokens --workload granite_h_micro_fit \
        --seeds 1,2,3 [--control] [--fault dropped_state] [--out file.jsonl]
"""
import sys

from benchmark.tests import faults_tokens, readings

readings.faults = faults_tokens

if __name__ == "__main__":
    sys.exit(readings.main())
