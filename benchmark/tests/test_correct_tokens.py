"""`correct` of the token cell shown to fail, at a size a test run can hold
(``tiny_tokens.py``), as ``test_correct.py`` shows it of the image cells:
a whole run of the harness with the timed path sound and then broken by
each planted fault, and the reference in the nearest precision below
(float32's is bfloat16) in the program's place.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_correct_tokens.py -q
"""
import argparse
import gc
import json

import pytest

from benchmark import compare, flops_tokens, run as harness
from benchmark.tests import faults_tokens, tiny_tokens


def result_line(capsys, fault):
    argv = ["--workload", tiny_tokens.CELL, "--seed", "7", "--seconds", "0.5",
            "--trace", "0", "--rehearse", tiny_tokens.overlay()]
    if fault is None:
        assert harness.main(argv) == 0
    else:
        with faults_tokens.plant(fault):
            assert harness.main(argv) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", [None, "unchanged", "half_tokens",
                                   "dropped_state", "wrong_pick"])
def test_faults_come_out_not_correct(capsys, fault):
    line = result_line(capsys, fault)
    assert line["correct"] is (fault is None), line["compared"]
    if fault is not None:
        failed = [n for n, c in line["compared"].items()
                  if c["limit"] is not None and c["value"] > c["limit"]]
        assert failed, line["compared"]
    if fault == "unchanged":
        assert line["compared"]["change"]["value"] == pytest.approx(1.0)
    if fault == "wrong_pick":    # the step is sound: the loss alone tells
        assert failed == ["loss_1"]


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_control_comes_out_not_correct(seed):
    ns = argparse.Namespace(workload=tiny_tokens.CELL, seed=seed, seconds=0,
                            trace=0, rehearse=tiny_tokens.overlay())
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


def test_operations_of_the_cell_are_the_issues_count():
    cfg = harness.load_json("benchmark", "configs",
                            "granite_4_0_h_micro_pp4_vp8.json")
    per_token = flops_tokens.train_flops(cfg, 1, 4096) / 4096
    assert 4.7e9 < per_token < 4.85e9
    assert flops_tokens.matrix_parameters(cfg) == 772160448 - 277440
    # the scan's four products: 17.4 GFLOP a layer forward at 4,096 tokens
    assert flops_tokens.ssd_forward_flops(cfg, 1, 4096) == pytest.approx(
        17.45e9, rel=1e-2)


def test_scope_readers_sum_the_events_the_hlo_text_names():
    """`scopes.py`: a trace names an event by its instruction, the
    program's HLO text gives the instruction's scope; wrappers (`while`)
    are left out, their bodies' events counted, and so is an event of the
    same name that another program ran between two steps; a program without
    the text, or a scope no event carries, reads None."""
    from benchmark import scopes
    text = '''
  %fusion.7 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, calls=%c, metadata={op_name="jit(step)/jit(main)/checkpoint/mamba_mixer/mamba2_ssd/mul" source_file="x.py"}
  ROOT %fusion.9 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, calls=%d, metadata={op_name="jit(step)/transpose(jvp(mamba2_ssd))/exp"}
  %while.3 = (f32[4]{0}) while(%t), condition=%a, body=%b, metadata={op_name="jit(step)/mamba2_ssd/while"}
  %copy.1 = f32[4]{0} copy(f32[4]{0} %p), metadata={op_name="jit(step)/optimizer/add"}
  %dot.2 = f32[4]{0} dot(%x, %y), metadata={op_name="jit(step)/attention/flash_attention/dot_general"}
'''
    names = scopes.op_names([text])
    assert names["fusion.9"].endswith("transpose(jvp(mamba2_ssd))/exp")
    ev = lambda name, ms, cat="fusion", at=0.0: {
        "name": name, "start": at, "end": at + ms * 1e6, "category": cat}

    class Run:
        trace_data = {"dispatches": 2, "step_program": "^jit_step", "chips": [{
            "modules": [ev("jit_step(1)", 200, "module"),
                        ev("jit__picked_log_sum(2)", 1, "module", at=2e8),
                        ev("jit_step(1)", 200, "module", at=3e8)],
            "ops": [
                ev("fusion.7", 3), ev("fusion.9", 5),
                ev("while.3", 100, "while"), ev("copy.1", 2, "copy"),
                ev("dot.2", 7, "dot", at=3e8), ev("fusion.1", 11),
                ev("fusion.7", 13, at=2e8)]}]}
        result = {"batches_per_dispatch": 1, "work": {"hlo_text": [text]}}

    assert scopes.device_ms_per_step(Run, "mamba2_ssd") == pytest.approx(4.0)
    assert scopes.device_ms_per_step(Run, "optimizer") == pytest.approx(1.0)
    assert scopes.device_ms_per_step(Run, "attention") == pytest.approx(3.5)
    assert scopes.device_ms_per_step(Run, "flash_attention") \
        == pytest.approx(3.5)
    assert scopes.device_ms_per_step(Run, "mlp") is None
    Run.result = {"batches_per_dispatch": 1, "work": {"hlo_text": None}}
    assert scopes.device_ms_per_step(Run, "mamba2_ssd") is None
