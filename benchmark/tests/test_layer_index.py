"""The attribution of `benchmark/layer_index.py` and its five readers, on a
hand-written HLO text recorded through the program's own compile hook
(`mxnet_tpu.shardprof.note_program`) and a hand-built trace with a known
answer in every layer:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_layer_index.py -q
"""
import types

import pytest

from benchmark import flops, layer_index, run as harness
from benchmark.refs import vgg
from mxnet_tpu import shardprof

STEP = r"^jit_step\("

HLO = """\
HloModule jit_step, entry_computation_layout={(bf16[8,8]{1,0})->f32[8]{0}}

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %sum = f32[] add(%a, %b), metadata={op_name="reduce_sum"}
}

%fused_conv_stats (param_0: bf16[8,8], param_1: bf16[8,8]) -> f32[8] {
  %param_0 = bf16[8,8]{1,0} parameter(0)
  %param_1 = bf16[8,8]{1,0} parameter(1)
  %convolution.1 = bf16[8,8]{1,0} convolution(%param_0, %param_1), \
dim_labels=bf_io->bf, \
metadata={op_name="jit(step)/jvp(convolution)/conv_general_dilated"}
  %convert.1 = f32[8,8]{1,0} convert(%convolution.1), \
metadata={op_name="jit(step)/jvp(batch_norm)/convert_element_type"}
  %c.1 = f32[] constant(0)
  ROOT %reduce.1 = f32[8]{0} reduce(%convert.1, %c.1), dimensions={0}, \
to_apply=%add, metadata={op_name="jit(step)/jvp(batch_norm)/reduce_sum"}
}

%fused_bn_bwd (param_0: bf16[8,8]) -> f32[8] {
  %param_0 = bf16[8,8]{1,0} parameter(0)
  %convert.2 = f32[8,8]{1,0} convert(%param_0), metadata={op_name=\
"jit(step)/transpose(jvp(batch_norm))/batch_norm/convert_element_type"}
  %c.2 = f32[] constant(0)
  ROOT %reduce.2 = f32[8]{0} reduce(%convert.2, %c.2), dimensions={0}, \
to_apply=%add, metadata={op_name=\
"jit(step)/transpose(jvp(batch_norm))/batch_norm/reduce_sum"}
}

%fused_dgrad_add (param_0: bf16[8,8], param_1: bf16[8,8]) -> bf16[8,8] {
  %param_0 = bf16[8,8]{1,0} parameter(0)
  %param_1 = bf16[8,8]{1,0} parameter(1)
  ROOT %dot.3 = bf16[8,8]{1,0} dot(%param_0, %param_1), \
lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name=\
"jit(step)/transpose(jvp(convolution))/conv_general_dilated"}
}

%fused_inner (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %max.4 = f32[8]{0} maximum(%param_0, %param_0), \
metadata={op_name="jit(step)/jvp(pooling)/max"}
}

%fused_outer (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %inner = f32[8]{0} fusion(%param_0), kind=kLoop, calls=%fused_inner
}

%fused_plain (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %add.5 = f32[8]{0} add(%param_0, %param_0), \
metadata={op_name="jit(step)/optimizer/add"}
}

ENTRY %main.9 (p0: bf16[8,8]) -> f32[8] {
  %p0 = bf16[8,8]{1,0} parameter(0)
  %convert_reduce_fusion = f32[8]{0} fusion(%p0, %p0), kind=kOutput, \
calls=%fused_conv_stats, \
metadata={op_name="jit(step)/jvp(batch_norm)/reduce_sum"}
  %bn_fusion = f32[8]{0} fusion(%p0), kind=kInput, calls=%fused_bn_bwd, \
metadata={op_name=\
"jit(step)/transpose(jvp(batch_norm))/batch_norm/reduce_sum"}
  %dot_fusion = bf16[8,8]{1,0} fusion(%p0, %p0), kind=kOutput, \
calls=%fused_dgrad_add
  %select-and-scatter.6 = bf16[8,8]{1,0} select-and-scatter(%p0, %p0), \
metadata={op_name="jit(step)/transpose(jvp(pooling))/select_and_scatter"}
  %pool_fusion = f32[8]{0} fusion(%convert_reduce_fusion), kind=kLoop, \
calls=%fused_outer
  %plain_fusion = f32[8]{0} fusion(%bn_fusion), kind=kLoop, \
calls=%fused_plain
  %convolution.7 = bf16[8,8]{1,0} convolution(%p0, %p0), \
dim_labels=bf_io->bf, \
metadata={op_name="jit(step)/jvp(convolution)/conv_general_dilated"}
  %copy.8 = bf16[8,8]{0,1} copy(%p0)
  ROOT %dot.9 = f32[8]{0} dot(%p0, %plain_fusion), \
metadata={op_name="jit(step)/jvp()/dot_general"}
}
"""

# instruction -> (layer, microseconds a step)
EXPECTED = {
    "convert_reduce_fusion": ("convolution", 700),   # conv + BN's sums
    "dot_fusion": ("convolution", 500),              # a dot under conv
    "convolution.7": ("convolution", 300),
    "bn_fusion": ("batch_norm", 400),
    "select-and-scatter.6": ("pooling", 200),
    "pool_fusion": ("pooling", 100),                 # a nested fusion
    "plain_fusion": (None, 60),                      # unscoped
    "copy.8": (None, 30),                            # no op_name
    "dot.9": (None, 10),                             # the FC layer's dot
    "not-in-index.10": (None, 5),                    # unknown instruction
}


class Compiled:
    def __init__(self, text):
        self.text = text

    def as_text(self):
        return self.text


@pytest.fixture
def recorded():
    shardprof.reset()
    shardprof.note_program("module.fused_step", ("module.fused_step", 1),
                           Compiled(HLO))
    yield
    shardprof.reset()


def hand_run(steps=2, cell="resnet50_fit"):
    """A run whose trace holds ``steps`` executions of the step program,
    each with every event of `EXPECTED` inside it, plus events that do not
    count: a ``while`` wrapper, and an op of another program between two
    steps under a name the index knows."""
    modules, ops, t = [], [], 0.0
    for _ in range(steps):
        start = t
        for name, (_, us) in EXPECTED.items():
            ops.append({"name": name, "start": t, "end": t + us * 1e3,
                        "category": "fusion"})
            t += us * 1e3
        ops.append({"name": "while.11", "start": start, "end": t,
                    "category": "while"})
        modules.append({"name": "jit_step(77)", "start": start, "end": t})
        t += 1e6
        ops.append({"name": "bn_fusion", "start": t - 5e5, "end": t - 1e5,
                    "category": "fusion"})
        modules.append({"name": "jit_other(3)", "start": t - 5e5,
                        "end": t - 1e5})
    layers = vgg.layers({"prefix": "vgg0_", "stack_convs": [1, 1, 1, 1, 1],
                         "stack_channels": [8, 8, 8, 8, 8], "hidden": 16,
                         "dropout": 0.0, "classes": 10})
    return types.SimpleNamespace(
        trace_data={"dispatches": steps, "step_program": STEP,
                    "window_s": t / 1e9,
                    "chips": [{"ops": ops, "modules": modules}]},
        result={"batches_per_dispatch": 1,
                "work": {"layers": layers, "shape": (4, 3, 32, 32),
                         "layout": "NCHW", "dtype": "bfloat16"}},
        peaks={"flops_per_s": {"bfloat16": 1e12}},
        cell={"name": cell, "chips": 1})


def want_ms(layer):
    return sum(us for lay, us in EXPECTED.values() if lay == layer) / 1e3


def read(name, run):
    return harness.load_module("layer_metrics", name).read(run)


def test_index_records_of_the_hand_text(recorded):
    ops = shardprof.op_index("jit_step")
    assert ops is not None and shardprof.op_index("jit_scan_step") is None
    conv = ops["convert_reduce_fusion"]
    assert conv["conv"] == "jit(step)/jvp(convolution)/conv_general_dilated"
    assert {"convolution", "batch_norm", "step"} <= conv["scopes"]
    assert "transpose(" in ops["dot_fusion"]["conv"]
    assert ops["bn_fusion"]["conv"] is None
    assert "batch_norm" in ops["bn_fusion"]["scopes"]
    assert "pooling" in ops["pool_fusion"]["scopes"]
    assert ops["copy.8"] == {"op_name": "", "scopes": frozenset(),
                             "conv": None}
    assert ops["dot.9"]["conv"] is None
    # fused instructions are not ops of their own
    assert "convolution.1" not in ops and "inner" not in ops


def test_every_event_goes_to_one_layer_by_precedence(recorded):
    run = hand_run()
    ops = shardprof.op_index("jit_step")
    for name, (layer, _) in EXPECTED.items():
        assert layer_index.layer_of(ops.get(name)) == layer, name
    got = layer_index.split(run)
    for layer in layer_index.LAYERS + (None,):
        assert got[layer] == pytest.approx(want_ms(layer)), layer
    assert read("conv.device_ms", run) == pytest.approx(1.5)
    assert read("bn.device_ms", run) == pytest.approx(0.4)
    assert read("pool.device_ms", run) == pytest.approx(0.3)
    share = read("step.unattributed_share", run)
    assert share == pytest.approx(105 / 2305 * 100)
    # the four tile the step program's op time
    total = sum(us for _, us in EXPECTED.values()) / 1e3
    assert 1.5 + 0.4 + 0.3 + share / 100 * total == pytest.approx(total)


def test_roofline_share_is_the_convolutions_flops_over_their_time(recorded):
    run = hand_run()
    work = run.result["work"]
    mats = flops.matmul_layers(work["layers"], work["shape"], work["layout"])
    conv_flops = flops.train_flops(mats, op="conv")
    assert conv_flops < flops.train_flops(mats)    # the FC layers are not in
    want = conv_flops / 1e12 / 1.5e-3 * 100
    assert read("conv.roofline_share", run) == pytest.approx(want)


def test_the_step_program_is_picked_by_the_mix(recorded):
    """The scan mix's program: another module name, another index."""
    shardprof.note_program("module.scan_step", ("module.scan_step", 1),
                           Compiled(HLO.replace("HloModule jit_step",
                                                "HloModule jit_scan_step")
                                    .replace("dot_fusion", "other_fusion")))
    assert read("conv.device_ms", hand_run()) == pytest.approx(1.5)
    run = hand_run()
    run.trace_data["step_program"] = r"^jit_scan_step\("
    for m in run.trace_data["chips"][0]["modules"]:
        m["name"] = m["name"].replace("jit_step(", "jit_scan_step(")
    # dot_fusion is unknown to the scan program's index: unattributed
    assert read("conv.device_ms", run) == pytest.approx(1.0)


NAMES = ("conv.device_ms", "conv.roofline_share", "bn.device_ms",
         "pool.device_ms", "step.unattributed_share")


@pytest.mark.parametrize("name", NAMES)
def test_every_reader_is_none_without_an_index_or_a_trace(name):
    shardprof.reset()
    assert read(name, hand_run()) is None
    shardprof.note_program("module.fused_step", ("module.fused_step", 1),
                           Compiled(HLO))
    try:
        run = hand_run()
        run.trace_data = None
        assert read(name, run) is None
    finally:
        shardprof.reset()


def test_a_layer_with_no_events_reads_none(recorded):
    run = hand_run()
    for e in run.trace_data["chips"][0]["ops"]:
        if e["name"] in ("select-and-scatter.6", "pool_fusion"):
            e["name"] = "plain_fusion"
    assert read("pool.device_ms", run) is None
    assert read("conv.device_ms", run) == pytest.approx(1.5)
