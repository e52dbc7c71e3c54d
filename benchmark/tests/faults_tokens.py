"""The faults a token cell can have, planted underneath the timed path for
the length of a ``with plant(name):`` (as `faults.py` plants the image
cells'): the harness above runs as it always does and has to see
``correct`` come out false.

  unchanged      the step computes its outputs and hands the parameters
                 back as it found them; the optimizer's state of the first
                 step is put back to zeros
  half_tokens    the second half of every row is left out: its ids and
                 labels are overwritten with the first half's
  dropped_state  the chunked state-space scan carries no state across chunk
                 boundaries: every chunk starts from zero (the planted
                 fault of this mechanism)
  wrong_pick     the metric's reduction on the device picks the probability
                 of the class after the label's: the loss `fit` reports is
                 not the label's (the step itself is sound)
"""
import contextlib

import numpy as np


def _halved(batch):
    import mxnet_tpu as mx

    def fold(arr):
        x = arr.asnumpy()
        half = x.shape[1] // 2
        x = np.concatenate([x[:, :half], x[:, :half]], axis=1)
        return mx.nd.array(x, ctx=arr.context, dtype=x.dtype)

    return mx.io.DataBatch(data=[fold(a) for a in batch.data],
                           label=[fold(a) for a in batch.label])


@contextlib.contextmanager
def plant(name):
    from mxnet_tpu import metric
    from mxnet_tpu.module.module import Module
    from mxnet_tpu.ops import ssm_ops
    step, carry = Module._step, ssm_ops._carry_states
    reduction = metric._picked_log_sum

    if name == "unchanged":
        def broken_step(self, batch):
            import jax
            import jax.numpy as jnp
            # the step may write over its parameters in place (donation):
            # what it found is kept in host memory
            weights = [self._exec.arg_dict[n] for n in self._param_names]
            kept = [(a, np.asarray(a._data), a._data.sharding)
                    for a in weights]
            fresh = not self._updater.states
            out = step(self, batch)
            for a, data, where in kept:
                a._data = jax.device_put(data, where)
            if fresh:
                for state in self._updater.states.values():
                    for s in state if isinstance(state, tuple) else (state,):
                        if s is not None:
                            s._data = jnp.zeros_like(s._data)
            return out
    elif name == "half_tokens":
        def broken_step(self, batch):
            return step(self, _halved(batch))
    elif name == "dropped_state":
        broken_step = step

        def dropped(states, chunk_decay):
            import jax.numpy as jnp
            return jnp.zeros_like(states)

        ssm_ops._carry_states = dropped
    elif name == "wrong_pick":
        broken_step = step

        def shifted(*args):
            reduce = reduction(*args)
            return lambda pred, label: reduce(
                pred, (label + 1) % pred.shape[-1])

        metric._picked_log_sum = shifted
    else:
        raise ValueError("no fault %r" % name)

    Module._step = broken_step
    try:
        yield
    finally:
        Module._step, ssm_ops._carry_states = step, carry
        metric._picked_log_sum = reduction
