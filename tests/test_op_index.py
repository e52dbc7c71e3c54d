"""The convolutional ops' named scopes (`ops/nn.py`: ``convolution``,
``batch_norm``, ``pooling``) and the op index the compile hook keeps
(`shardprof.note_program` -> `shardprof.op_index`): what a device trace's
op events are joined to, to split a step's device time by layer.

A small ResNet-style symbol (convolution, BatchNorm, relu, residual add, max
pooling, fully connected, softmax) goes through `Module.fit` on the CPU, one
batch to a dispatch and two. Its compiled step carries the scopes on the
instructions of each op, forward and backward; the hook's index is the
parse of that compiled text; recording it compiles nothing; and the step
with its metadata stripped is instruction for instruction the step built
without the scopes.
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import shardprof, xla_stats
from mxnet_tpu.ops import nn

INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = \S.*?\s([a-z][\w\-]*)\(.*$", re.M)
OP_NAME = re.compile(r'op_name="([^"]*)"')


def _net():
    data = mx.sym.Variable("data")
    c1 = mx.sym.Convolution(data, num_filter=8, kernel=(3, 3), pad=(1, 1),
                            no_bias=True, name="c1")
    b1 = mx.sym.BatchNorm(c1, fix_gamma=False, name="bn1")
    r1 = mx.sym.Activation(b1, act_type="relu")
    c2 = mx.sym.Convolution(r1, num_filter=8, kernel=(3, 3), pad=(1, 1),
                            no_bias=True, name="c2")
    b2 = mx.sym.BatchNorm(c2, fix_gamma=False, name="bn2")
    r2 = mx.sym.Activation(b2 + r1, act_type="relu")
    pool = mx.sym.Pooling(r2, kernel=(2, 2), stride=(2, 2), pool_type="max")
    fc = mx.sym.FullyConnected(pool, num_hidden=4, name="fc")
    return mx.sym.SoftmaxOutput(fc, name="softmax")


def _fit(k):
    rng = np.random.RandomState(0)
    x = rng.randn(32, 3, 8, 8).astype(np.float32)
    y = (rng.rand(32) * 4).astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=8, label_name="softmax_label")
    mod = mx.mod.Module(_net(), context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            batches_per_dispatch=k)
    program = mod.step_program() if k == 1 \
        else next(iter(mod._scan_plans.values()))
    (text,) = program.compiled_text()
    return mod, text


def _unscoped(monkeypatch, build):
    """``build()`` with every `jax.named_scope` a no-op."""
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        return build()


def _instructions(text):
    """[(name, opcode, op_name)] of every instruction of an HLO text."""
    out = []
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if m is not None:
            op_name = OP_NAME.search(line)
            out.append((m.group(1), m.group(2),
                        op_name.group(1) if op_name else ""))
    return out


def _stripped(text):
    """The text's computations with their metadata (and the stack-frame
    tables ahead of them) left out."""
    lines, body = [], False
    for line in text.splitlines():
        body = body or re.match(r"^(ENTRY )?%", line) is not None
        if body or line.startswith("HloModule"):
            lines.append(re.sub(r", metadata=\{[^}]*\}", "", line))
    return "\n".join(lines)


def _under(op_name, scope):
    return re.search(r"(^|[/(])%s([/)]|$)" % scope, op_name) is not None


@pytest.fixture(scope="module", params=[1, 2], ids=["per_batch", "scan2"])
def fitted(request):
    shardprof.reset()
    mod, text = _fit(request.param)
    return request.param, mod, text


def test_every_convolution_carries_its_scope(fitted):
    _, _, text = fitted
    convs = [(n, o) for n, code, o in _instructions(text)
             if code == "convolution"]
    # the forward of both, and their gradients to input and weight
    assert len(convs) >= 5, convs
    ops = shardprof.op_index_of(text)
    for name, op_name in convs:
        # the CPU's passes rebuild the weight gradients' convolutions and
        # drop their metadata: the index counts those as convolutions too
        assert op_name == "" or _under(op_name, "convolution"), \
            (name, op_name)
        if name in ops:
            assert ops[name]["conv"] is not None, name
    assert len([o for _, o in convs if o and "transpose(" not in o]) == 2
    # the backward's instructions carry it too (where the CPU kept them)
    assert "transpose(jvp(convolution))" in text


def test_batch_norm_forward_and_backward_reductions_carry_its_scope(fitted):
    _, _, text = fitted
    reduces = [o for _, code, o in _instructions(text) if code == "reduce"]
    forward = [o for o in reduces
               if _under(o, "batch_norm") and "transpose(" not in o]
    backward = [o for o in reduces if "transpose(" in o
                and re.search(r"\)/batch_norm/", o)]
    # two sums a layer each way: the moments, and the custom VJP's sums
    # under the scope its backward rule opens itself
    assert len(forward) >= 4 and len(backward) >= 4, reduces
    pooled = [o for _, code, o in _instructions(text)
              if _under(o, "pooling")]
    assert any("transpose(" in o for o in pooled)


def test_op_index_is_the_parse_of_the_compiled_text(fitted):
    k, _, text = fitted
    module = "jit_step" if k == 1 else "jit_scan_step"
    assert text.startswith("HloModule %s," % module)
    ops = shardprof.op_index(module)
    assert ops is not None and ops == shardprof.op_index_of(text)
    convs = [r["conv"] for r in ops.values() if r["conv"] is not None]
    assert convs and all(c == "" or _under(c, "convolution") for c in convs)
    assert any("batch_norm" in r["scopes"] and r["conv"] is None
               for r in ops.values())
    assert any("pooling" in r["scopes"] for r in ops.values())
    entry = [e for e in shardprof.programs().values()
             if e.get("module") == module]
    assert entry and entry[0]["parse_s"] >= 0


@pytest.mark.parametrize("k", [1, 2])
def test_recording_adds_no_compile(monkeypatch, k):
    monkeypatch.setenv("MXNET_SHARDPROF", "0")
    c0 = xla_stats.compile_counts()
    _fit(k)
    off = xla_stats.compile_counts()["compiles"] - c0["compiles"]
    monkeypatch.setenv("MXNET_SHARDPROF", "1")
    c0 = xla_stats.compile_counts()
    mod, _ = _fit(k)
    on = xla_stats.compile_counts()
    assert on["compiles"] - c0["compiles"] == off > 0
    # nor does reading the index, or training on with it read
    shardprof.op_index("jit_step")
    shardprof.op_index("jit_scan_step")
    shardprof.snapshot()
    rng = np.random.RandomState(1)
    it = mx.io.NDArrayIter(rng.randn(32, 3, 8, 8).astype(np.float32),
                           (rng.rand(32) * 4).astype(np.float32),
                           batch_size=8, label_name="softmax_label")
    mod.fit(it, num_epoch=1, batches_per_dispatch=k)
    after = xla_stats.compile_counts()
    assert (after["compiles"], after["retraces"]) \
        == (on["compiles"], on["retraces"])


def test_scopes_leave_the_optimized_program_as_it_was(fitted, monkeypatch):
    k, _, text = fitted
    _, bare = _unscoped(monkeypatch, lambda: _fit(k))
    assert "jvp(convolution)" in text and "jvp(convolution)" not in bare
    assert _stripped(text) == _stripped(bare)


def _vjp_text(f, *args):
    """The compiled text of ``f``'s output and its vjp at ``args``, the
    cotangent an argument of its own."""
    def both(ct, *a):
        out, back = jax.vjp(f, *a)
        return out, back(ct)
    ct = jax.eval_shape(f, *args)
    return jax.jit(both).lower(jnp.ones(ct.shape, ct.dtype), *args) \
        .compile().as_text()


def _conv(layout):
    shape = (2, 8, 8, 3) if layout == "NHWC" else (2, 3, 8, 8)
    wshape = (4, 3, 3, 3)
    params = {"kernel": (3, 3), "pad": (1, 1), "stride": (2, 2),
              "no_bias": False, "layout": layout}

    def f(x, w, b):
        return nn._convolution(params, x, w, b)[0]
    return f, (jnp.ones(shape), jnp.ones(wshape) * 0.1, jnp.ones((4,)))


def _batch_norm():
    mm, mv = jnp.zeros((3,)), jnp.ones((3,))

    def f(x, g, b):
        return nn._batch_norm({"_is_train": True, "fix_gamma": False,
                               "axis": 1, "eps": 1e-5}, x, g, b, mm, mv)[0]
    return f, (jnp.arange(96.0).reshape(2, 3, 4, 4) / 96, jnp.ones((3,)),
               jnp.zeros((3,)))


def _pool(pool_type):
    params = {"kernel": (2, 2), "stride": (2, 2), "pool_type": pool_type,
              "count_include_pad": False, "pad": (1, 1)}

    def f(x):
        return nn._pooling(params, x)[0]
    return f, (jnp.arange(128.0).reshape(2, 4, 4, 4),)


@pytest.mark.parametrize("scope, make", [
    ("convolution", lambda: _conv("NCHW")),
    ("convolution", lambda: _conv("NHWC")),
    ("batch_norm", _batch_norm),
    ("pooling", lambda: _pool("max")),
    ("pooling", lambda: _pool("avg")),
], ids=["conv_nchw", "conv_nhwc", "batch_norm", "pool_max", "pool_avg"])
def test_an_op_alone_carries_its_scope_on_every_instruction(scope, make):
    """Forward and backward of the op alone: every instruction traced
    under the jit carries the op's scope."""
    f, args = make()
    traced = [(n, o) for n, _, o in _instructions(_vjp_text(f, *args))
              if o.startswith("jit(")]
    assert traced
    for name, op_name in traced:
        assert _under(op_name, scope), (name, op_name)
    assert any("transpose(" in o for _, o in traced)


HLO = """\
HloModule jit_scan_step, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_a (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %mul.1 = f32[8]{0} multiply(%param_0, %param_0), \
metadata={op_name="jit(scan_step)/while/body/jvp(batch_norm)/mul"}
}

%fused_b (param_0: f32[8,8], param_1: f32[8,8]) -> f32[8,8] {
  %param_0 = f32[8,8]{1,0} parameter(0)
  %param_1 = f32[8,8]{1,0} parameter(1)
  %inner = f32[8,8]{1,0} fusion(%param_0), kind=kLoop, calls=%fused_c
  ROOT %dot.2 = f32[8,8]{1,0} dot(%inner, %param_1), \
lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name=\
"jit(scan_step)/while/body/transpose(jvp(convolution))/conv_general_dilated"}
}

%fused_c (param_0: f32[8,8]) -> f32[8,8] {
  %param_0 = f32[8,8]{1,0} parameter(0)
  ROOT %neg.3 = f32[8,8]{1,0} negate(%param_0), \
metadata={op_name="jit(scan_step)/while/body/jvp(pooling)/neg"}
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %x.4 = f32[8]{0} get-tuple-element(%p), index=1
  %bn_fusion = f32[8]{0} fusion(%x.4), kind=kLoop, calls=%fused_a
  %conv_fusion = f32[8,8]{1,0} fusion(%w, %w), kind=kOutput, \
calls=%fused_b, metadata={op_name="jit(scan_step)/while/body/add"}
  ROOT %t = (s32[], f32[8]{0}) tuple(%i, %bn_fusion)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %ar = f32[8]{0} all-reduce(%a), channel_id=1, replica_groups=[1,2]<=[2]
  %while.5 = (s32[], f32[8]{0}) while(%init), condition=%cond, body=%body
  ROOT %copy.6 = f32[8]{0} copy(%a)
}
"""


def test_op_index_of_a_scanned_program():
    module, collectives, ops = shardprof.read_hlo(HLO)
    assert module == "jit_scan_step"
    assert collectives == shardprof.parse_hlo_collectives(HLO)
    assert [c["kind"] for c in collectives] == ["all-reduce"]
    # the while body's ops are ops of their own; fused instructions are not
    assert {"bn_fusion", "conv_fusion", "while.5", "copy.6", "ar"} <= set(ops)
    assert not {"mul.1", "dot.2", "inner", "neg.3"} & set(ops)
    bn, conv = ops["bn_fusion"], ops["conv_fusion"]
    assert bn["conv"] is None and "batch_norm" in bn["scopes"]
    # a dot under the convolution's scope counts as its convolution, and a
    # nested fusion's scopes are its caller's
    assert "transpose(jvp(convolution))" in conv["conv"]
    assert {"convolution", "pooling", "body", "while", "scan_step"} \
        <= conv["scopes"]
    assert conv["op_name"] == "jit(scan_step)/while/body/add"
    assert ops["copy.6"] == {"op_name": "", "scopes": frozenset(),
                             "conv": None}


class _Compiled:
    def __init__(self, text):
        self.text = text

    def as_text(self):
        return self.text


def test_op_index_latest_compile_wins_and_is_off_with_the_hook(monkeypatch):
    shardprof.reset()
    try:
        assert shardprof.op_index("jit_scan_step") is None
        shardprof.note_program("s", ("s", 1), _Compiled(HLO))
        first = shardprof.op_index("jit_scan_step")
        assert "bn_fusion" in first
        shardprof.note_program("s", ("s", 2), _Compiled(
            HLO.replace("bn_fusion", "bn_fusion.7")))
        assert "bn_fusion.7" in shardprof.op_index("jit_scan_step")
        assert shardprof.op_index("jit_step") is None
        monkeypatch.setenv("MXNET_SHARDPROF", "0")
        shardprof.reset()
        assert shardprof.note_program("s", ("s", 1), _Compiled(HLO)) is None
        assert shardprof.op_index("jit_scan_step") is None
    finally:
        shardprof.reset()
