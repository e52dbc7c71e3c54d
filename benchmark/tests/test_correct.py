"""`correct` shown to fail, at a size a test run can hold (``tiny.py``):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_correct.py -q

One test drives a whole run of the harness, past its look for a chip, with
the timed path sound and then broken underneath by each fault a training
cell can have, and reads ``correct`` off the result line. The other puts the
reference, computed in the nearest precision below the configuration's, in
the program's place: the control has to come out as not correct."""
import argparse
import gc
import json

import pytest

from benchmark import compare, reference, run as harness
from benchmark.tests import faults, tiny


def result_line(capsys, fault, k):
    argv = ["--workload", tiny.CELL, "--seed", "7", "--seconds", "0.5",
            "--trace", "0", "--rehearse", tiny.overlay(k)]
    if fault is None:
        assert harness.main(argv) == 0
    else:
        with faults.plant(fault):
            assert harness.main(argv) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("k", [1, 2])
@pytest.mark.parametrize("fault", [None, "unchanged", "half_batch"])
def test_faults_come_out_not_correct(capsys, fault, k):
    line = result_line(capsys, fault, k)
    assert list(line)[-1] == "compared"
    assert line["correct"] is (fault is None), line["compared"]
    if fault is not None:
        assert line["failed"] == line["attempted"] > 0
        failed = [n for n, c in line["compared"].items()
                  if c["limit"] is not None and c["value"] > c["limit"]]
        assert failed, line["compared"]
    if fault == "unchanged":
        assert line["compared"]["change"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("seed", [3, 4, 2**31 + 5])
def test_control_comes_out_not_correct(seed):
    ns = argparse.Namespace(workload=tiny.CELL, seed=seed, seconds=0,
                            trace=0, rehearse=tiny.overlay())
    run = harness.Run(ns, harness.load_json("BENCHMARK.json"))
    entry = harness.load_module("entries", run.traffic["entry"])
    entry.quiet()
    job = entry.Job(run)
    program, side = job.first_steps(), job.reference_side()
    del job
    gc.collect()
    ref, w0 = side()
    below = reference.BELOW[reference.POLICY[run.config["dtype"]]]
    control, _ = side(below)
    limits, opt = run.limits["limits"], run.config["optimizer"]
    sound, _, lines = compare.judge(
        compare.numbers(program, ref, w0, opt), limits)
    assert sound, lines
    held, _, lines = compare.judge(
        compare.numbers(control, ref, w0, opt), limits)
    assert not held, lines
