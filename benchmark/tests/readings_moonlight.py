"""`readings.py` for the Moonlight cell: the same readings, many seeds in
one process, with this cell's faults (`faults_moonlight.py`) to plant:

    python -m benchmark.tests.readings_moonlight \
        --workload moonlight_fit --seeds 1,2,3 [--control] \
        [--fault half_tokens] [--out file.jsonl]
"""
import sys

from benchmark.tests import faults_moonlight, readings

readings.faults = faults_moonlight

if __name__ == "__main__":
    sys.exit(readings.main())
