"""`correct` of the SmallThinker cell shown to fail, at a size a test run
can hold (``tiny_smallthinker.py``), as ``test_correct_tokens.py`` shows it
of the Granite cell: a whole run of the harness with the timed path sound
and then broken by each planted fault, and the reference in the nearest
precision below (float32's is bfloat16) in the program's place.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_correct_smallthinker.py -q
"""
import argparse
import gc
import json

import pytest

from benchmark import compare, flops_moe, run as harness
from benchmark.tests import faults_smallthinker, tiny_smallthinker

FAULTS = ["unchanged", "half_tokens", "window_dropped", "rope_dropped",
          "router_post_attention", "held_offset"]


def result_line(capsys, fault):
    argv = ["--workload", tiny_smallthinker.CELL, "--seed", "7",
            "--seconds", "0.5", "--trace", "0",
            "--rehearse", tiny_smallthinker.overlay()]
    if fault is None:
        assert harness.main(argv) == 0
    else:
        with faults_smallthinker.plant(fault):
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
    ns = argparse.Namespace(workload=tiny_smallthinker.CELL, seed=seed,
                            seconds=0, trace=0,
                            rehearse=tiny_smallthinker.overlay())
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
    """25.9 TFLOP a step at two rows of 8,192: the window layers admit
    three quarters of the causal pairs; the held experts see a quarter of
    the six assignments a token."""
    cfg = harness.load_json("benchmark", "configs",
                            "smallthinker_21b_a3b_ep4_v8.json")
    assert flops_moe.train_flops(cfg, 2, 8192) == pytest.approx(25.95e12,
                                                                rel=1e-3)
    causal = 8192 * 8193 // 2
    assert flops_moe.attention_pairs(cfg, 8192) \
        == causal + 3 * (8192 * 4096 - 4096 * 4095 // 2)
    assert 3 * (8192 * 4096 - 4096 * 4095 // 2) / (3 * causal) \
        == pytest.approx(0.75, abs=1e-3)
    assert flops_moe.expected_assignments(cfg, 2, 8192) == 16384 * 6 / 4
    # a window wider than the row admits every causal pair
    assert flops_moe.attention_pairs(dict(cfg, sliding_window_size=9000),
                                     8192) == 4 * causal


def test_grouped_kernels_count_under_the_experts_scopes():
    """`grouped.py`: the kernels XLA lowers `ragged_dot` to carry no
    ``op_name``; they are found by their instruction's name, inside the
    step program only, and added to the events under the scope, which
    `scopes.py` alone would miss."""
    from benchmark import grouped, scopes
    text = '''
  %fusion.4 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, calls=%c, metadata={op_name="jit(step)/moe/moe/moe_experts/max"}
  %sort.2 = s32[4]{0} sort(s32[4]{0} %p), metadata={op_name="jit(step)/moe/moe/sort"}
  %ragged-dot-none.3 = bf16[4]{0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %fusion.8 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, calls=%d, metadata={op_name="jit(step)/attention/dot_general"}
'''
    ev = lambda name, ms, cat="fusion", at=0.0: {
        "name": name, "start": at, "end": at + ms * 1e6, "category": cat}

    class Run:
        trace_data = {"dispatches": 2, "step_program": "^jit_step", "chips": [{
            "modules": [ev("jit_step(1)", 200, "module"),
                        ev("jit_step(1)", 200, "module", at=3e8)],
            "ops": [ev("fusion.4", 3), ev("sort.2", 5, "sort"),
                    ev("ragged-dot-none.3", 7, "custom-call"),
                    ev("ragged-dot-metadata", 1, "custom-call", at=3e8),
                    ev("ragged-dot-none.3", 11, "custom-call", at=2.5e8),
                    ev("fusion.8", 13)]}]}
        result = {"batches_per_dispatch": 1, "work": {"hlo_text": [text]}}

    assert scopes.device_ms_per_step(Run, "moe_experts") == pytest.approx(1.5)
    assert grouped.device_ms_per_step(Run, "moe_experts") \
        == pytest.approx((3 + 7 + 1) / 2)
    assert grouped.device_ms_per_step(Run, "moe") \
        == pytest.approx((3 + 5 + 7 + 1) / 2)
    Run.result = {"batches_per_dispatch": 1, "work": {"hlo_text": None}}
    assert grouped.device_ms_per_step(Run, "moe") is None
