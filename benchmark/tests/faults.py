"""The faults a training cell can have, planted underneath the timed path:
`Module._step` and `Module._step_scan` are patched for the length of a
``with plant(name):`` and restored after it. The harness above them runs as
it always does and has to see ``correct`` come out false.

  unchanged   the step computes its outputs and returns its state as it
              found it: parameters, optimizer state and BatchNorm statistics
  half_batch  the second half of every batch is left out and the mean taken
              over the rest: its rows are overwritten with the first half's,
              which gives every sum, mean and batch statistic of the first
              half alone
"""
import contextlib

import numpy as np


def _halved(batch):
    import mxnet_tpu as mx

    def fold(arr):
        x = arr.asnumpy()
        half = x.shape[0] // 2
        x = np.concatenate([x[:half], x[:half]][: 2])
        return mx.nd.array(x, ctx=arr.context, dtype=x.dtype)

    return mx.io.DataBatch(data=[fold(a) for a in batch.data],
                           label=[fold(a) for a in batch.label])


def _state(mod):
    weights = [mod._exec.arg_dict[n] for n in mod._param_names]
    aux = list(mod._exec.aux_dict.values())
    states = [s for s in mod._updater.states.values() if s is not None]
    return weights + aux + states


@contextlib.contextmanager
def plant(name):
    from mxnet_tpu.module.module import Module
    step, scan = Module._step, Module._step_scan

    if name == "unchanged":
        def frozen(self, call, batches):
            import jax.numpy as jnp
            # the first step creates the optimizer's state: let it, then
            # put back zeros
            kept = [(a, jnp.copy(a._data)) for a in _state(self)]
            fresh = not self._updater.states
            out = call(self, batches)
            for a, data in kept:
                a._data = data
            if fresh:
                for s in self._updater.states.values():
                    if s is not None:
                        s._data = jnp.zeros_like(s._data)
            return out

        def broken_step(self, batch):
            return frozen(self, step, batch)

        def broken_scan(self, batches):
            return frozen(self, scan, batches)
    elif name == "half_batch":
        def broken_step(self, batch):
            return step(self, _halved(batch))

        def broken_scan(self, batches):
            return scan(self, [_halved(b) for b in batches])
    else:
        raise ValueError("no fault %r" % name)

    Module._step, Module._step_scan = broken_step, broken_scan
    try:
        yield
    finally:
        Module._step, Module._step_scan = step, scan
