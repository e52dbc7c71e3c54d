"""Imperative autograd: tape-based reverse-mode differentiation.

Parity with reference `python/mxnet/autograd.py` (record/pause/train_mode/
predict_mode/backward/grad/Function) and the C++ tape in
`src/imperative/imperative.cc:182,358` (RecordOp/Backward).

Design (TPU-native): instead of re-building an NNVM gradient graph, each
recorded op captures its `jax.vjp` closure at dispatch time — the residuals
live as device buffers, and backward is a reverse topological sweep calling
the stored vjps. This matches XLA's functional model: no gradient graph pass,
no kAddTo buffers; accumulation is functional adds.
"""
from __future__ import annotations

import threading

import numpy as np
import jax

from .base import MXNetError

__all__ = ["record", "pause", "train_mode", "predict_mode", "is_recording",
           "is_training", "backward", "grad", "mark_variables", "Function"]


class _AGState(threading.local):
    def __init__(self):
        super().__init__()
        self.recording = False
        self.training = False
        self.node_count = 0


_STATE = _AGState()


class _RecordingScope:
    def __init__(self, recording, training):
        self._rec = recording
        self._train = training
        self._saved = None

    def __enter__(self):
        self._saved = (_STATE.recording, _STATE.training)
        if self._rec is not None:
            _STATE.recording = self._rec
        if self._train is not None:
            _STATE.training = self._train
        return self

    def __exit__(self, *a):
        _STATE.recording, _STATE.training = self._saved
        return False


def record(train_mode=True):  # noqa: D401 - reference API name
    """`with autograd.record():` — reference autograd.py:103."""
    return _RecordingScope(True, train_mode)


def pause(train_mode=False):
    return _RecordingScope(False, train_mode)


def train_mode():
    return _RecordingScope(None, True)


def predict_mode():
    return _RecordingScope(None, False)


def is_recording():
    return _STATE.recording


def is_training():
    return _STATE.training


def set_recording(flag):
    prev = _STATE.recording
    _STATE.recording = flag
    return prev


def set_training(flag):
    prev = _STATE.training
    _STATE.training = flag
    return prev


class Node:
    """One recorded op on the tape (reference AGInfo, imperative.h:59-95).

    ``fwd_fn`` (when present) is the pure JAX function the node was recorded
    from; ``grad(..., create_graph=True)`` replays it so gradients stay
    differentiable (the vjp closure alone hides the residuals' dependency on
    the primals)."""

    __slots__ = ("vjp_fn", "inputs", "out_shapes", "out_dtypes", "seq",
                 "name", "fwd_fn", "in_vals")

    def __init__(self, vjp_fn, inputs, out_shapes, out_dtypes, name="",
                 fwd_fn=None, in_vals=None):
        self.vjp_fn = vjp_fn
        self.inputs = inputs            # list[NDArray]
        # snapshot the (immutable) jax buffers at record time: in-place
        # NDArray mutation rebinds ._data, so replay for create_graph must
        # not read the inputs' *current* buffers (they may have moved on).
        # Only replayable nodes need it (fwd_fn-less custom Functions
        # reject create_graph anyway; don't pin their buffers).
        if fwd_fn is None:
            self.in_vals = None
        else:
            self.in_vals = [a._data for a in inputs] if in_vals is None \
                else list(in_vals)
        self.out_shapes = out_shapes
        self.out_dtypes = out_dtypes
        self.name = name
        self.fwd_fn = fwd_fn
        _STATE.node_count += 1
        self.seq = _STATE.node_count


def _zero_cotangent(shape, dtype, device=None):
    dtype = np.dtype(dtype)
    if np.issubdtype(dtype, np.inexact):
        import jax.numpy as jnp
        # place on the tape's device: a default-device zeros would drag the
        # whole vjp through a cross-device transfer on remote-TPU platforms
        return jnp.zeros(shape, dtype, device=device)
    # integer/bool outputs carry float0 cotangents in JAX
    return np.zeros(shape, jax.dtypes.float0)


def backward(heads, head_grads=None, retain_graph=False, train_mode=True):
    """Run backward from output NDArrays, accumulating into leaf ``.grad``.

    Mirrors reference `Imperative::Backward` (imperative.cc:358): default head
    gradient is ones for each head; grads land in arrays attached by
    ``attach_grad`` honoring their grad_req (write/add/null).
    """
    from .ndarray.ndarray import NDArray  # late import, avoids cycle
    import jax.numpy as jnp

    if isinstance(heads, NDArray):
        heads = [heads]
    if head_grads is None:
        head_grads = [None] * len(heads)
    elif isinstance(head_grads, NDArray):
        head_grads = [head_grads]
    if len(heads) != len(head_grads):
        raise MXNetError("heads and head_grads length mismatch")

    # Collect reachable nodes.
    nodes = {}

    def visit(node):
        if node is None or node.seq in nodes:
            return
        stack = [node]
        while stack:
            n = stack.pop()
            if n.seq in nodes:
                continue
            nodes[n.seq] = n
            for x in n.inputs:
                if x._autograd_node is not None:
                    stack.append(x._autograd_node[0])

    # cotangent accumulators: per node -> list per output; per leaf id -> value
    node_cots = {}
    leaf_cots = {}
    leaves = {}

    def add_cot(arr, cot):
        if arr._autograd_node is not None:
            node, idx = arr._autograd_node
            store = node_cots.setdefault(node.seq, [None] * len(node.out_shapes))
            store[idx] = cot if store[idx] is None else store[idx] + cot
        if arr._requires_grad:
            key = id(arr)
            leaves[key] = arr
            leaf_cots[key] = cot if key not in leaf_cots else leaf_cots[key] + cot

    # Pin JAX's default device to the tape's device for the whole replay:
    # eager transpose rules and head/zero cotangents materialize constants
    # (lax.full etc.) on the DEFAULT device, and on a TPU host every such
    # constant for a cpu-context tape would be a device round trip.
    from .base import device_of
    tape_dev = None
    for h in heads:
        tape_dev = device_of(h._data)
        if tape_dev is not None:
            break

    import contextlib
    # a Sharding (SPMD tape) can't pin jax's default device; constants then
    # materialize on the default device and ops reshard them as needed
    dev_scope = jax.default_device(tape_dev) \
        if tape_dev is not None and not hasattr(tape_dev, "device_set") \
        else contextlib.nullcontext()
    with dev_scope:
        any_tape = False
        for h, hg in zip(heads, head_grads):
            if h._autograd_node is None and not h._requires_grad:
                continue
            any_tape = True
            if h._autograd_node is not None:
                visit(h._autograd_node[0])
            if hg is None:
                cot = jnp.ones(h.shape, h.dtype, device=device_of(h._data))
            else:
                cot = hg._data
            add_cot(h, cot)
        if not any_tape:
            raise MXNetError(
                "this array is not attached to any computation graph; "
                "run operations inside autograd.record() first")

        for seq in sorted(nodes, reverse=True):
            node = nodes[seq]
            cots = node_cots.get(seq)
            if cots is None:
                continue
            dev = None
            for x in node.inputs:
                dev = device_of(getattr(x, "_data", None))
                if dev is not None:
                    break
            full = [c if c is not None else _zero_cotangent(s, d, dev)
                    for c, (s, d) in
                    zip(cots, zip(node.out_shapes, node.out_dtypes))]
            if node.vjp_fn is None:
                raise MXNetError(
                    "computation graph was already freed by a previous "
                    "backward; pass retain_graph=True to backward() to "
                    "keep it")
            in_cots = node.vjp_fn(tuple(full))
            for x, c in zip(node.inputs, in_cots):
                if c is None or (hasattr(c, "dtype")
                                 and c.dtype == jax.dtypes.float0):
                    continue
                add_cot(x, c)
            node_cots.pop(seq, None)

    # write into .grad respecting grad_req
    for key, arr in leaves.items():
        if arr.grad is None or arr._grad_req == "null":
            continue
        cot = leaf_cots[key].astype(arr.dtype)
        if arr._grad_req == "add":
            arr.grad._data = arr.grad._data + cot
        else:
            arr.grad._data = cot

    if not retain_graph:
        for node in nodes.values():
            node.vjp_fn = None
            node.inputs = ()


def grad(heads, variables, head_grads=None, retain_graph=None, create_graph=False,
         train_mode=True):
    """Reference `autograd.grad` (autograd.py:270-291): return grads of heads
    w.r.t. variables; with ``create_graph=True`` the returned grads are
    themselves on the tape, so a second ``backward``/``grad`` differentiates
    through them (higher-order gradients)."""
    from .ndarray.ndarray import NDArray

    if create_graph:
        return _grad_taped(heads, variables, head_grads,
                           train_mode=train_mode)
    single = isinstance(variables, NDArray)
    if single:
        variables = [variables]
    saved = [(v._requires_grad, v._grad_req, v.grad) for v in variables]
    for v in variables:
        v.attach_grad("write")
    try:
        backward(heads, head_grads, retain_graph=bool(retain_graph), train_mode=train_mode)
        out = [v.grad.copy() for v in variables]
    finally:
        for v, (req, greq, g) in zip(variables, saved):
            v._requires_grad = req
            v._grad_req = greq
            v.grad = g
    return out[0] if single else out


def _grad_taped(heads, variables, head_grads=None, train_mode=True):
    """``grad(..., create_graph=True)``: backward sweep whose cotangent
    computation is ITSELF recorded on the tape.

    Each tape node's backward is replayed as the pure JAX function
    ``(primals, out_cots) -> in_cots`` (via ``jax.vjp`` over the node's
    recorded ``fwd_fn``), so the in-cotangents stay differentiable w.r.t.
    both the incoming cotangents AND the primals (the residual dependency
    that a captured vjp closure would hide). Cotangent accumulation runs on
    NDArrays under ``record()`` so the adds are taped too. The original
    tape is retained (create_graph implies retain_graph)."""
    from .ndarray.ndarray import NDArray, _from_data
    import jax.numpy as jnp
    from .base import device_of

    single_v = isinstance(variables, NDArray)
    if single_v:
        variables = [variables]
    if isinstance(heads, NDArray):
        heads = [heads]
    if head_grads is None:
        head_grads = [None] * len(heads)
    elif isinstance(head_grads, NDArray):
        head_grads = [head_grads]
    var_ids = {id(v) for v in variables}

    nodes = {}

    def visit(node):
        stack = [node]
        while stack:
            n = stack.pop()
            if n.seq in nodes:
                continue
            nodes[n.seq] = n
            for x in n.inputs:
                if x._autograd_node is not None:
                    stack.append(x._autograd_node[0])

    node_cots = {}
    leaf_cots = {}

    def add_cot(arr, cot_nd):
        if arr._autograd_node is not None:
            node, idx = arr._autograd_node
            store = node_cots.setdefault(node.seq,
                                         [None] * len(node.out_shapes))
            store[idx] = cot_nd if store[idx] is None else store[idx] + cot_nd
        if id(arr) in var_ids or arr._requires_grad:
            key = id(arr)
            leaf_cots[key] = cot_nd if key not in leaf_cots \
                else leaf_cots[key] + cot_nd

    tape_dev = None
    for h in heads:
        tape_dev = device_of(h._data)
        if tape_dev is not None:
            break
    import contextlib
    # a Sharding (SPMD tape) can't pin jax's default device; constants then
    # materialize on the default device and ops reshard them as needed
    dev_scope = jax.default_device(tape_dev) \
        if tape_dev is not None and not hasattr(tape_dev, "device_set") \
        else contextlib.nullcontext()

    with dev_scope, _RecordingScope(True, train_mode):
        any_tape = False
        for h, hg in zip(heads, head_grads):
            if h._autograd_node is None and not h._requires_grad \
                    and id(h) not in var_ids:
                continue
            any_tape = True
            if h._autograd_node is not None:
                visit(h._autograd_node[0])
            if hg is None:
                cot = _from_data(jnp.ones(h.shape, h.dtype,
                                          device=device_of(h._data)), h.ctx)
            else:
                cot = hg
            add_cot(h, cot)
        if not any_tape:
            raise MXNetError(
                "this array is not attached to any computation graph; "
                "run operations inside autograd.record() first")

        for seq in sorted(nodes, reverse=True):
            node = nodes[seq]
            cots = node_cots.get(seq)
            if cots is None:
                continue
            if node.fwd_fn is None:
                raise MXNetError(
                    "create_graph=True over a node with no replayable "
                    "forward (%s); custom autograd.Function does not "
                    "support higher-order gradients" % (node.name,))
            n_in = len(node.inputs)
            out_float = [np.issubdtype(np.dtype(d), np.inexact)
                         for d in node.out_dtypes]
            in_float = [np.issubdtype(np.dtype(x.dtype), np.inexact)
                        for x in node.inputs]
            # materialize missing output cotangents as zero NDArrays
            full = []
            for c, s, d, isf in zip(cots, node.out_shapes, node.out_dtypes,
                                    out_float):
                if c is not None or not isf:
                    full.append(c)
                else:
                    full.append(_from_data(
                        jnp.zeros(s, d, device=device_of(
                            node.inputs[0]._data) if node.inputs else None),
                        node.inputs[0].ctx if node.inputs else None))
            cot_nds = [c for c, isf in zip(full, out_float) if isf and
                       c is not None]

            fwd_fn = node.fwd_fn
            shapes_dtypes = list(zip(node.out_shapes, node.out_dtypes))

            def bwd_as_fn(*args, _fwd=fwd_fn, _n=n_in, _of=tuple(out_float),
                          _sd=tuple(shapes_dtypes), _if=tuple(in_float)):
                primals, in_cots = args[:_n], args[_n:]
                _, vjp = jax.vjp(lambda *p: _fwd(*p), *primals)
                filled, it = [], iter(in_cots)
                for isf, (s, d) in zip(_of, _sd):
                    if isf:
                        filled.append(next(it))
                    else:
                        filled.append(np.zeros(s, jax.dtypes.float0))
                out = vjp(tuple(filled))
                return tuple(c for c, keep in zip(out, _if) if keep)

            arg_nds = list(node.inputs) + cot_nds
            # inputs use the record-time snapshot (ADVICE r2: current ._data
            # may have been rebound by in-place mutation since recording)
            vals = list(node.in_vals) + [c._data for c in cot_nds]
            for v in vals:
                if getattr(v, "is_deleted", lambda: False)():
                    raise MXNetError(
                        "create_graph replay over node %s: a recorded input "
                        "buffer was donated/deleted (e.g. by a fused "
                        "optimizer step) after recording; higher-order "
                        "gradients must be taken before in-place donation "
                        "of the tape's inputs" % (node.name,))
            raw_outs, vjp2 = jax.vjp(bwd_as_fn, *vals)
            keep_inputs = [x for x, keep in zip(node.inputs, in_float)
                           if keep]
            # the replay node must snapshot the SAME record-time buffers,
            # not arg_nds' current ._data (which may have moved on) — else
            # the mutation bug reappears one derivative order higher
            new_node = Node(lambda cts, _v=vjp2: _v(tuple(cts)),
                            arg_nds,
                            [o.shape for o in raw_outs],
                            [o.dtype for o in raw_outs],
                            name=node.name + "_backward",
                            fwd_fn=bwd_as_fn,
                            in_vals=vals)
            for i, (x, rc) in enumerate(zip(keep_inputs, raw_outs)):
                cot_nd = _from_data(rc, x.ctx)
                cot_nd._autograd_node = (new_node, i)
                add_cot(x, cot_nd)
            node_cots.pop(seq, None)

    out = []
    for v in variables:
        g = leaf_cots.get(id(v))
        if g is None:
            g = _from_data(jnp.zeros(v.shape, v.dtype,
                                     device=device_of(v._data)), v.ctx)
        out.append(g.astype(v.dtype) if g.dtype != v.dtype else g)
    return out[0] if single_v else out


def mark_variables(variables, gradients, grad_reqs="write"):
    """Reference `autograd.mark_variables`."""
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for v, g, req in zip(variables, gradients, grad_reqs):
        v._requires_grad = True
        v._grad_req = req
        v.grad = g


def get_symbol(x):  # pragma: no cover - graph introspection stub
    raise NotImplementedError("autograd.get_symbol: use Symbol tracing instead")


class Function:
    """Customized differentiable function (reference autograd.py Function).

    Subclass and implement ``forward(self, *inputs)`` and
    ``backward(self, *output_grads)`` over NDArrays.
    """

    def __init__(self):
        self._saved = None

    def save_for_backward(self, *arrays):
        self._saved = arrays

    @property
    def saved_tensors(self):
        return self._saved

    def forward(self, *inputs):
        raise NotImplementedError

    def backward(self, *output_grads):
        raise NotImplementedError

    def __call__(self, *inputs):
        from .ndarray.ndarray import NDArray, _wrap_like

        with pause():
            outputs = self.forward(*inputs)
        single = isinstance(outputs, NDArray)
        outs = [outputs] if single else list(outputs)
        if is_recording():
            fn = self

            def vjp_fn(cots):
                from .ndarray.ndarray import array as _nd_array
                with pause():
                    cot_nds = [_wrap_like(c, o) for c, o in zip(cots, outs)]
                    in_grads = fn.backward(*cot_nds)
                if isinstance(in_grads, NDArray):
                    in_grads = [in_grads]
                return [g._data if g is not None else None for g in in_grads]

            node = Node(vjp_fn, list(inputs),
                        [o.shape for o in outs], [o.dtype for o in outs],
                        name=type(self).__name__)
            for i, o in enumerate(outs):
                o._autograd_node = (node, i)
        return outputs
