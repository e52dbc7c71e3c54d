"""The faults the SmallThinker cell can have, planted underneath the timed
path for the length of a ``with plant(name):`` (as `faults_tokens.py`
plants the token cells'): the harness above runs as it always does and has
to see ``correct`` come out false.

  window_dropped         every layer attends to every earlier key: the
                         sliding window is not applied
  rope_dropped           no rotary term in any layer
  router_post_attention  the router reads the experts' input (the norm of
                         the residual after attention) in place of the
                         layer's input
  held_offset            the layer computes experts first_held + 1 on with
                         the weights of first_held on: the held experts
                         offset by one
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
    if name == "window_dropped":
        def unbounded(*args, window=None, **kwargs):
            return flash(*args, **kwargs)
        pallas_kernels.flash_attention = unbounded
    elif name == "rope_dropped":
        contrib_ops.rope = lambda x, theta: x
    elif name == "router_post_attention":
        def after(router_x, x, *args, **kwargs):
            return moe(x, x, *args, **kwargs)
        moe_ops.moe = after
    elif name == "held_offset":
        def offset(*args, first_held, **kwargs):
            return moe(*args, first_held=first_held + 1, **kwargs)
        moe_ops.moe = offset
    else:
        raise ValueError("no fault %r" % name)
    try:
        yield
    finally:
        pallas_kernels.flash_attention, contrib_ops.rope, moe_ops.moe = \
            flash, rope, moe
