"""The faults the Moonlight cell can have, planted underneath the timed
path for the length of a ``with plant(name):`` (as `faults_tokens.py`
plants the token cells'): the harness above runs as it always does and has
to see ``correct`` come out false.

  rope_dropped           no rotary term on the queries' or the shared key's
                         rotary dims
  scale_dropped          the routed experts' weights are not scaled by
                         routed_scaling_factor
  held_offset            the layer computes experts first_held + 1 on with
                         the weights of first_held on: the held experts
                         offset by one
  narrow_scale           attention's scores scaled by 1 / sqrt(value
                         width) in place of 1 / sqrt(query-key width)
  unchanged, half_tokens as in `faults_tokens.py`
"""
import contextlib

from benchmark.tests import faults_tokens


@contextlib.contextmanager
def plant(name):
    if name in ("unchanged", "half_tokens"):
        with faults_tokens.plant(name):
            yield
        return
    from mxnet_tpu.ops import contrib_ops, moe_ops, pallas_kernels
    flash, rope, moe = (pallas_kernels.flash_attention, contrib_ops.rope,
                        moe_ops.moe)
    if name == "rope_dropped":
        contrib_ops.rope = lambda x, theta: x
    elif name == "scale_dropped":
        def unscaled(*args, routed_scale=1.0, **kwargs):
            return moe(*args, **kwargs)
        moe_ops.moe = unscaled
    elif name == "held_offset":
        def offset(*args, first_held, **kwargs):
            return moe(*args, first_held=first_held + 1, **kwargs)
        moe_ops.moe = offset
    elif name == "narrow_scale":
        def narrow(q, k, v, scale=None, **kwargs):
            return flash(q, k, v, scale=v.shape[-1] ** -0.5, **kwargs)
        pallas_kernels.flash_attention = narrow
    else:
        raise ValueError("no fault %r" % name)
    try:
        yield
    finally:
        pallas_kernels.flash_attention, contrib_ops.rope, moe_ops.moe = \
            flash, rope, moe
