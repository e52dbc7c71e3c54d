"""`correct` of the Moonlight cell shown to fail, at a size a test run can
hold (``tiny_moonlight.py``), as ``test_correct_smallthinker.py`` shows it
of the SmallThinker cell: a whole run of the harness with the timed path
sound and then broken by each planted fault, and the reference in the
nearest precision below (float32's is bfloat16) in the program's place.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_correct_moonlight.py -q
"""
import argparse
import gc
import json

import numpy as np
import pytest

from benchmark import compare, flops_mla, run as harness
from benchmark.refs import moonlight as ref
from benchmark.tests import faults_moonlight, tiny_moonlight

FAULTS = ["unchanged", "half_tokens", "rope_dropped", "scale_dropped",
          "held_offset", "narrow_scale"]


def result_line(capsys, fault):
    argv = ["--workload", tiny_moonlight.CELL, "--seed", "7",
            "--seconds", "0.5", "--trace", "0",
            "--rehearse", tiny_moonlight.overlay()]
    if fault is None:
        assert harness.main(argv) == 0
    else:
        with faults_moonlight.plant(fault):
            assert harness.main(argv) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", [None] + FAULTS)
def test_faults_come_out_not_correct(capsys, fault):
    line = result_line(capsys, fault)
    assert line["correct"] is (fault is None), line["compared"]
    if fault is not None:
        failed = [n for n, c in line["compared"].items()
                  if c["limit"] is not None and c["value"] > c["limit"]]
        assert failed, line["compared"]


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_control_comes_out_not_correct(seed):
    ns = argparse.Namespace(workload=tiny_moonlight.CELL, seed=seed,
                            seconds=0, trace=0,
                            rehearse=tiny_moonlight.overlay())
    run = harness.Run(ns, harness.load_json("BENCHMARK.json"))
    entry = harness.load_module("entries", run.traffic["entry"])
    entry.quiet()
    job = entry.Job(run)
    program, side = job.first_steps(), job.reference_side()
    del job
    gc.collect()
    ref, w0 = side()
    control, _ = side("bf16")
    limits, opt = run.limits["limits"], run.config["optimizer"]
    sound, _, lines = compare.judge(
        compare.numbers(program, ref, w0, opt), limits)
    assert sound, lines
    held, _, lines = compare.judge(
        compare.numbers(control, ref, w0, opt), limits)
    assert not held, lines


def test_operations_of_the_cell_follow_its_shapes():
    """43.2 TFLOP a step at two rows of 8,192: attention's kernels 12.4 of
    them at 2 (192 + 128) operations a causal pair and head; the held
    experts see an eighth of the six assignments a token."""
    cfg = harness.load_json("benchmark", "configs",
                            "moonlight_16b_a3b_ep8_v8.json")
    assert flops_mla.train_flops(cfg, 2, 8192) == pytest.approx(43.17e12,
                                                                rel=1e-3)
    causal = 8192 * 8193 // 2
    assert flops_mla.attention_pairs(cfg, 8192) == 6 * causal
    assert flops_mla.attention_train_flops(cfg, 2, 8192) \
        == 3 * 2 * (192 + 128) * 16 * 2 * 6 * causal
    assert flops_mla.expected_assignments(cfg, 2, 8192) == 16384 * 6 / 8
    # every matrix parameter outside the held experts and the embedding
    shapes = ref.param_shapes(cfg)
    matrices = sum(int(np.prod(s)) for n, s in shapes.items()
                   if len(s) == 2 and n != "embed_weight")
    assert flops_mla.dense_parameters(cfg) == matrices
