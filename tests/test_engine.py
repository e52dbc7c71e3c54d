"""Engine fence semantics (reference Engine::WaitForAll,
include/mxnet/engine.h:219): `fence` / `waitall` / `wait_to_read` wait on
the buffers themselves (`block_until_ready`), compile nothing of their
own, and skip buffers that were donated away."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import engine


def test_fence_leaves_every_array_ready():
    a = jnp.ones((64, 64), jnp.float32)
    outs = [a @ a, (a * 2).sum(), jnp.tanh(a).astype(jnp.bfloat16)]
    engine.fence(outs)
    assert all(o.is_ready() for o in outs)


def test_fence_skips_deleted_buffers_only():
    # a donated buffer is deleted between live_arrays() and the wait:
    # that is not a failure, and the live ones are still waited for
    gone = jnp.ones((4,), jnp.float32)
    kept = jnp.ones((4,), jnp.float32) + 1
    gone.delete()
    engine.fence([gone, kept])
    assert gone.is_deleted() and kept.is_ready()

    class Broken:
        def block_until_ready(self):
            raise RuntimeError("device halted")

        def is_deleted(self):
            return False

    with pytest.raises(RuntimeError, match="device halted"):
        engine.fence([Broken()])


def test_fence_handles_empty_and_int_arrays():
    engine.fence([jnp.zeros((0,), jnp.float32), jnp.arange(3),
                  jnp.ones((2, 2), bool), np.ones(3)])


def test_waitall_is_idempotent_across_steps(monkeypatch):
    # the fence is a wait, not a program: it must never reach jit
    def no_jit(*a, **k):
        raise AssertionError("fence compiled a program")
    for step in range(3):
        x = mx.nd.ones((4, 4)) * (step + 1)
        y = (x * 2).sum()
        with monkeypatch.context() as m:
            m.setattr(jax, "jit", no_jit)
            mx.nd.waitall()
            mx.nd.waitall()
            y.wait_to_read()
        assert float(y.asnumpy()) == 32.0 * (step + 1)


def test_fence_mixed_single_and_sharded():
    """waitall over a live set mixing single-device and mesh-sharded arrays
    (SPMD module training) waits for every shard of every array."""
    import numpy as onp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel.mesh import make_mesh
    mesh = make_mesh({"dp": 8})
    sharded = jax.device_put(onp.ones((16, 4), onp.float32),
                             NamedSharding(mesh, P("dp"))) * 3
    repl = jax.device_put(onp.ones((4,), onp.float32),
                          NamedSharding(mesh, P()))
    single = jnp.ones((4, 4), jnp.float32)
    engine.fence([sharded, repl, single, sharded])
    assert sharded.is_ready() and len(sharded.devices()) == 8
    assert all(s.data.is_ready() for s in sharded.addressable_shards)
