"""Chip-free compiles of the main-path kernels and programs at real widths
(/opt/skills/guides/on-chip-measurement §2.3): the TPU's compiler is
installed here and compiles for a v5e that is described, not attached.
Nothing runs; what the chip's compiler would refuse, it refuses here, so a
jax bump or a kernel edit fails a test and not a chip run.

All of them live in this ONE file: the worker that gets it loads libtpu
and keeps it until it exits. The topology is described inside the
module-scoped fixture below and nowhere else; no child process compiles.

The program asks `jax.default_backend()` where it chooses between a Pallas
kernel and its fallback (`pallas_kernels.is_tpu`), and that is `cpu` under
test: the `on_tpu` fixture steers it, so what is compiled is what the chip
runs, not the interpret-mode path.
"""
import os

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import mxnet_tpu as mx
from mxnet_tpu import compiled
from mxnet_tpu.ops import pallas_kernels as pk


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cache_off():
    """A described device's executable cannot be read back from the
    persistent cache; keep it off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch, cache_off):
    monkeypatch.setattr(pk, "is_tpu", lambda: True)


def _compile(fn, *specs):
    exe = jax.jit(fn).lower(*specs).compile()
    print(exe.memory_analysis())
    return exe


def _on(sharding, tree):
    """Shapes of ``tree``'s arrays, placed on the described chip."""
    def spec(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
        return x
    return jax.tree.map(spec, tree)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("grad", [False, True], ids=["forward", "grad"])
def test_fused_lstm_ptb_medium(one_chip, on_tpu, dtype, grad):
    """LSTM-PTB 2x650's layer: T35 B32 H650. The forward is one Pallas
    kernel; `jax.grad` adds the backward kernel."""
    T, B, H = 35, 32, 650
    shapes = [(T, B, H), (B, H), (B, H), (H, 4 * H), (H, 4 * H), (4 * H,)]
    specs = [jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)
             for s in shapes]
    assert pk._lstm_bwd_fits_vmem(B, H)   # this side of the selection
    fn = pk.fused_lstm
    if grad:
        fn = jax.grad(lambda *a: pk.fused_lstm(*a)[0]
                      .astype(jnp.float32).sum(), argnums=(0, 3, 4, 5))
    text = _compile(fn, *specs).as_text()
    assert text.count("tpu_custom_call") == (2 if grad else 1)


def test_flash_attention_long_causal(one_chip, on_tpu):
    qkv = jax.ShapeDtypeStruct((1, 4096, 8, 128), jnp.bfloat16,
                               sharding=one_chip)
    exe = _compile(lambda q, k, v: pk.flash_attention(q, k, v, causal=True),
                   qkv, qkv, qkv)
    assert exe.as_text().count("tpu_custom_call") == 1


def test_resnet50_hybridized_forward_bs32(one_chip, on_tpu):
    """`__graft_entry__.entry()`'s forward, fp32 NCHW, at the benchmark's
    batch of 32. (Its net lives on `mx.cpu()`, so the stem stays a plain
    7x7 convolution: the next test compiles the rewrite.)"""
    import __graft_entry__ as graft
    forward, (params, x) = graft.entry()
    x32 = jax.ShapeDtypeStruct((32,) + x.shape[1:], x.dtype,
                               sharding=one_chip)
    exe = _compile(forward, _on(one_chip, params), x32)
    mem = exe.memory_analysis()
    assert mem.argument_size_in_bytes > 100 << 20   # the weights, ~98 MiB
    assert mem.temp_size_in_bytes < 8 << 30


def test_stem_space_to_depth_on_a_tpu_context(one_chip, on_tpu):
    """ResNet-50's stem on `mx.tpu()`: 7x7 stride 2 over 3 channels is
    rewritten to 4x4 stride 1 over 12 (`ops/nn.py` `_s2d_eligible`),
    which is what a net hybridized on the chip compiles."""
    def stem(x, w):
        ctx = mx.tpu()
        return mx.nd.Convolution(
            mx.nd.NDArray(x, ctx), mx.nd.NDArray(w, ctx), kernel=(7, 7),
            num_filter=64, stride=(2, 2), pad=(3, 3), no_bias=True)._data

    lowered = jax.jit(stem).lower(
        jax.ShapeDtypeStruct((32, 3, 224, 224), jnp.float32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((64, 3, 7, 7), jnp.float32, sharding=one_chip))
    assert "tensor<64x12x4x4xf32>" in lowered.as_text()
    print(lowered.compile().memory_analysis())


class _Captured(Exception):
    """Carries the arguments of the dispatch the test stopped."""


def test_resnet50_module_train_step_bf16_bs128(one_chip, on_tpu,
                                               monkeypatch, tmp_path):
    """The benchmark's ResNet-50 program at `chip_smoke.py`'s batch:
    `Module` over ResNet-50 NHWC, bf16 params and data, batch 128, the fused fwd+bwd+SGD-momentum `_step`. Built
    as chip_smoke.py's train phase builds it; the one dispatch is
    stopped at the CompiledProgram and compiled for the chip instead."""
    import chip_smoke
    cfg = chip_smoke.FULL
    sym_json, params_file = chip_smoke._export_resnet(
        cfg, "NHWC", str(tmp_path / "net"))
    mod, params = chip_smoke._resnet_module(cfg, mx.tpu(), sym_json,
                                            params_file)

    def capture(program, *args):
        raise _Captured(program, args)

    monkeypatch.setattr(compiled.CompiledProgram, "__call__", capture)
    batch, size = cfg["train_batch"], cfg["image"]
    it = mx.io.NDArrayIter(
        np.zeros((batch, size, size, 3), jnp.bfloat16),
        np.zeros((batch,), np.float32), batch_size=batch,
        label_name="softmax_label")
    with pytest.raises(_Captured) as stopped:
        chip_smoke._fit(mod, it, params, num_epoch=1)
    program, args = stopped.value.args
    assert program.site == "module.fused_step"
    exe = program.lower(*_on(one_chip, args)).compile()
    mem = exe.memory_analysis()
    print(mem)
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes
    assert total < 15 << 30, "the step must fit one v5e chip's 16 GB"


def test_mamba2_ssd_scan_at_published_widths(one_chip, on_tpu):
    """The chunked state-space scan of one Granite-4.0-H Mamba-2 layer at
    the benchmark's size (4,096 tokens, 64 heads of 64, state 128, chunks
    of 256), forward and backward in bfloat16: it compiles for the chip
    and its temporaries stay under 2 GiB (a layer is recomputed beside
    11.5 GiB of state)."""
    from mxnet_tpu.ops import ssm_ops
    bf16, f32 = jnp.bfloat16, jnp.float32
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)
    args = (spec((1, 4096, 64, 64), bf16), spec((1, 4096, 64), f32),
            spec((64,), f32), spec((1, 4096, 1, 128), bf16),
            spec((1, 4096, 1, 128), bf16), spec((64,), f32))

    def loss(*a):
        return jnp.sum(ssm_ops.mamba2_ssd(*a, 256).astype(f32))

    exe = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5)), *args)
    assert exe.memory_analysis().temp_size_in_bytes < 2 << 30


def test_flash_attention_grouped_backward_in_query_blocks(one_chip, on_tpu):
    """Granite-4.0-H's attention layer at 4,096 tokens: 32 query heads
    over 8 key-value heads of 64, the model's own scale. The backward pass
    goes over query blocks, so its temporaries are a block's scores (32 x
    512 x 4096 float32, 0.25 GiB, and what autodiff keeps of them) and not
    T x T for all heads (2 GiB for the scores alone)."""
    bf16 = jnp.bfloat16
    spec = lambda heads: jax.ShapeDtypeStruct((1, 4096, heads, 64), bf16,
                                              sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(pk.flash_attention(q, k, v, scale=1 / 64, causal=True)
                       .astype(jnp.float32))

    exe = _compile(jax.grad(loss, argnums=(0, 1, 2)), spec(32), spec(8),
                   spec(8))
    assert exe.memory_analysis().temp_size_in_bytes < 1 << 30
