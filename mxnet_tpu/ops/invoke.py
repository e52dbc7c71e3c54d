"""Eager operator dispatch.

TPU-native analog of `Imperative::Invoke` (reference
`src/imperative/imperative.cc:86`): resolve the op, run its JAX compute
(XLA dispatches asynchronously — the engine push in
`imperative_utils.h:343` is subsumed by PJRT), and if autograd is recording,
capture the `jax.vjp` closure as the tape node (reference RecordOp,
`imperative.cc:182`).
"""
from __future__ import annotations

from functools import partial

import jax

from .. import autograd, engine
from .registry import get_op

__all__ = ["invoke", "suppress_aux_writeback"]

import contextlib
import threading

_TLS = threading.local()


@contextlib.contextmanager
def suppress_aux_writeback():
    """Run ops WITHOUT committing mutate-aux updates (BN moving stats).
    Used by HybridBlock's warmup forward: the compiled call that follows
    performs the same update, and a double step would diverge from the
    eager trajectory."""
    prev = getattr(_TLS, "no_aux", False)
    _TLS.no_aux = True
    try:
        yield
    finally:
        _TLS.no_aux = prev


def _n_outputs(op, params):
    return op.n_out(params)


def invoke(op_name, inputs, params=None, out=None, name=None, ctx=None):
    """Run an op eagerly over NDArray inputs; returns NDArray or list."""
    from ..ndarray.ndarray import NDArray, _from_data

    op = get_op(op_name)
    params = dict(params) if params else {}
    in_arrs = list(inputs)
    vals = [x._data for x in in_arrs]
    if ctx is None:
        ctx = in_arrs[0].ctx if in_arrs else None

    if "_ctx" not in params:
        # ops that choose a lowering per device (fused kernels) read this;
        # filtered out of every static-attr cache key by the _ prefix
        params["_ctx"] = ctx
    if op.need_train_flag and "_is_train" not in params:
        params["_is_train"] = autograd.is_training()
    if op.need_rng and "_rng_key" not in params:
        from .. import random as _random
        params["_rng_key"] = _random.next_key(ctx)

    n_out = _n_outputs(op, params)
    n_aux = len(op.mutate_aux)

    recording = autograd.is_recording() and any(
        x._autograd_node is not None or x._requires_grad for x in in_arrs)

    from .. import profiler
    _prof_t0 = None
    if profiler.aggregate_enabled():
        import time as _time
        _prof_t0 = _time.perf_counter()
    if recording:
        fn = partial(_apply, op, params)
        raw_outs, vjp_fn = jax.vjp(fn, *vals)
    else:
        raw_outs = _apply(op, params, *vals)
        vjp_fn = None
    if _prof_t0 is not None:
        # aggregate-stats mode (reference aggregate_stats.cc): per-op
        # wall time + output bytes; synchronizes the dispatch. Tracer
        # outputs mean we're inside a jit trace — that wall time is
        # compile work, not a dispatch; don't pollute the table with it.
        leaves = raw_outs if isinstance(raw_outs, (tuple, list)) \
            else (raw_outs,)
        if not any(isinstance(v, jax.core.Tracer) for v in leaves):
            profiler.finish_timed(op_name, _prof_t0, raw_outs)
    if not isinstance(raw_outs, (tuple, list)):
        raw_outs = (raw_outs,)

    # write back mutated aux inputs (reference mutable aux states)
    if n_aux:
        if not getattr(_TLS, "no_aux", False):
            for aux_idx, new_val in zip(op.mutate_aux, raw_outs[n_out:]):
                in_arrs[aux_idx]._data = new_val
        raw_outs = raw_outs[:n_out]

    out_arrs = [_from_data(v, ctx) for v in raw_outs]
    if engine.is_naive():
        for o in out_arrs:
            engine.maybe_sync(o._data)

    if recording:
        node = autograd.Node(
            lambda cots: vjp_fn(tuple(cots)),
            in_arrs,
            [o.shape for o in out_arrs] + [a.shape for a in _aux_arrs(in_arrs, op)],
            [o.dtype for o in out_arrs] + [a.dtype for a in _aux_arrs(in_arrs, op)],
            name=op.name, fwd_fn=fn,
            # the mutate-aux writeback above already rebound in_arrs'
            # ._data; snapshot the PRE-mutation buffers the vjp was taken
            # over, or create_graph replay sees post-step aux state
            in_vals=vals)
        # note: vjp was taken over ALL fcompute outputs (incl. aux updates);
        # aux outputs receive zero cotangents via backward's fill logic.
        for i, o in enumerate(out_arrs):
            o._autograd_node = (node, i)

    if out is not None:
        outs = out if isinstance(out, (list, tuple)) else [out]
        for dst, src in zip(outs, out_arrs):
            dst._data = src._data.astype(dst.dtype) if dst.dtype != src.dtype else src._data
            if autograd.is_recording() and src._autograd_node is not None:
                dst._autograd_node = src._autograd_node
        return out

    if len(out_arrs) == 1:
        return out_arrs[0]
    return out_arrs


def _aux_arrs(in_arrs, op):
    # an op whose auxiliary input is optional (`moe`'s selection bias) may
    # be called without it
    return [in_arrs[i] for i in op.mutate_aux if i < len(in_arrs)]


def _apply(op, params, *vals):
    return op.fcompute(params, *vals)
