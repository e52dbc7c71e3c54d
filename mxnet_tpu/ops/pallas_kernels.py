"""Hand-written Pallas TPU kernels for the hot ops.

TPU-native replacement for the reference's handwritten CUDA/cuDNN kernels:
flash attention stands in for fused attention, and the fused LSTM layer
kernel replaces cuDNN's fused RNN (`src/operator/cudnn_rnn-inl.h` in the
reference). On non-TPU backends every kernel runs through the Pallas
interpreter, so the same code path is testable on CPU.

Design notes (see /opt/skills/guides/pallas_guide.md):
- flash attention: grid over (batch*heads, schedule entries), where a
  schedule lists only the (q block, k block) pairs that hold a pair that
  counts (not wholly above the diagonal, not wholly below a sliding
  window), in the sweep's order, prefetched into SMEM; the streaming
  softmax's stats and accumulator live in f32 VMEM scratch. The backward
  is two kernels (dK/dV over q blocks, dQ over k blocks) that rebuild each
  score block from q, k and the forward's log-sum-exp in VMEM.
- fused LSTM: the input projection x@Wx for ALL timesteps is one big MXU
  matmul outside the kernel; the kernel walks time on the grid with h/c
  held in VMEM scratch, doing only the recurrent h@Wh matmul per step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import telemetry

__all__ = ["flash_attention", "fused_lstm", "is_tpu"]

_NEG = -1e30


def _cast(x, dt):
    # Mosaic's convert_element_type lowering recurses forever on an
    # identity cast, so only emit the convert when dtypes differ
    return x if x.dtype == dt else x.astype(dt)


def is_tpu():
    """Whether programs lower for a TPU by default. A backend that fails
    to initialize raises here: answering "interpret" would run every
    kernel through the interpreter on a host whose chip is broken."""
    return jax.default_backend() == "tpu"


def _interpret():
    return not is_tpu()


# ---------------------------------------------------------------- attention

_LANES = 128


def _lanes_bcast(x, n):
    """Broadcast a lane-replicated (bq, 128) stat to n columns."""
    if n == _LANES:
        return x
    if n < _LANES:
        return x[:, :n]
    if n % _LANES:
        raise NotImplementedError("width %d not a multiple of %d"
                                  % (n, _LANES))
    return jnp.tile(x, (1, n // _LANES))


def _clamp(block, t):
    # a block wider than the sequence is clamped to it, then rounded down
    # to a lane multiple: the in-kernel lane broadcast only supports widths
    # that are multiples of 128 (or below one lane group); padding fills
    # out the final partial block
    block = min(block, t)
    if block > _LANES:
        block = (block // _LANES) * _LANES
    return block


def _pad_to(x, t):
    """Zero-pad axis 1 of ``x`` to length ``t``."""
    if x.shape[1] == t:
        return x
    pad = [(0, 0)] * x.ndim
    pad[1] = (0, t - x.shape[1])
    return jnp.pad(x, pad)


# the flags of a schedule entry (`_schedule`)
_FIRST, _LAST, _EDGE, _EMPTY = 1, 2, 4, 8


def _schedule(*, causal, window, block_q, block_k, seq_q, seq_k, k_major):
    """The (q block, k block) pairs a kernel visits, in the order of its
    sweep: q-major with k ascending (the forward, dQ) or k-major with q
    ascending (dK/dV). A pair is listed when it holds a (query, key) pair
    that counts: causal, inside the window (query i sees the keys j with
    i - window < j <= i) and neither one padding. `_FIRST` and `_LAST`
    mark the ends of each output block's run and `_EDGE` a listed pair
    that also holds one that does not count, the only ones the kernels
    mask. An output block with no pair that counts gets one `_EMPTY`
    entry, which writes it as zeros. Returns int32 arrays (q blocks,
    k blocks, flags); without ``causal`` the table is the dense grid."""
    nq, nk = -(-seq_q // block_q), -(-seq_k // block_k)
    q0 = np.arange(nq)[:, None] * block_q
    k0 = np.arange(nk)[None, :] * block_k
    q1 = np.minimum(q0 + block_q, seq_q) - 1          # last query, last key
    k1 = np.minimum(k0 + block_k, seq_k) - 1
    runs = np.ones((nq, nk), bool)
    whole = (q0 + block_q <= seq_q) & (k0 + block_k <= seq_k)
    if causal:
        runs &= k0 <= q1
        whole &= k1 <= q0
    if window is not None:
        runs &= q0 - k1 < window
        whole &= q1 - k0 < window
    if k_major:
        runs, whole = runs.T, whole.T
    qb, kb, fl = [], [], []
    for out, row in enumerate(runs):
        inner = np.flatnonzero(row)
        flags = np.where(whole[out, inner], 0, _EDGE)
        if not len(inner):
            inner, flags = np.zeros(1, int), np.array([_EMPTY])
        flags[0] |= _FIRST
        flags[-1] |= _LAST
        outer = np.full(len(inner), out)
        qb.append(inner if k_major else outer)
        kb.append(outer if k_major else inner)
        fl.append(flags)
    return tuple(np.concatenate(x).astype(np.int32) for x in (qb, kb, fl))


# the index maps of a kernel's blocks, from the entry's (q block i, k
# block j). They return j*0 instead of a literal 0: the package runs with
# jax_enable_x64 (base.py), under which a Python int lowers as i64, and
# Mosaic cannot legalize an i64 in the index map's func.return; j*0 stays
# i32 like the table's entries (tests/test_chip_compile.py compiles these
# for v5e)
def _at_q(b, i, j):
    return b, i, j * 0


def _at_k(b, i, j):
    return b, j, i * 0


def _sweep(kernel, name, table, dense, bh, in_specs, out_specs, out_shape,
           scratch_shapes, *args):
    """``kernel`` over the grid (row-heads, entries of ``table``), the
    table prefetched into SMEM. Each spec is (block shape, index map of
    the entry's block indices). ``dense``: the steps of the (q block,
    k block) grid, for ``flash_attention_block_steps_total``."""
    qb, kb, fl = table
    for kind, steps in (("run", len(fl)), ("dense", dense)):
        telemetry.counter(
            "flash_attention_block_steps_total",
            help="flash attention's grid steps a lowering, by kernel: the "
                 "schedule's (run) against the dense grid's (dense)",
            kernel=name, kind=kind).inc(bh * steps)

    def spec(shape, index):
        return pl.BlockSpec(shape, lambda b, s, qb, kb, fl: index(
            b, qb[s], kb[s]), memory_space=pltpu.VMEM)

    kernel = functools.partial(kernel, edges=bool((fl & _EDGE).any()),
                               empties=bool((fl & _EMPTY).any()))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(bh, len(fl)),
            in_specs=[spec(*s) for s in in_specs],
            out_specs=[spec(*s) for s in out_specs],
            scratch_shapes=scratch_shapes),
        out_shape=out_shape,
        interpret=_interpret(),
    )(qb, kb, fl, *args)


def _entry(qb_ref, kb_ref, fl_ref):
    """This grid step's q block, k block and flags."""
    s = pl.program_id(1)
    return qb_ref[s], kb_ref[s], fl_ref[s]


def _visit(body, flags, *, edges, empties):
    """Run ``body(masked)`` on a schedule entry: masked on an `_EDGE` one,
    not at all on an `_EMPTY` one. ``edges`` and ``empties`` say whether
    the table holds any, so a table without them traces no test."""
    if edges:
        pl.when((flags & _EDGE) != 0)(lambda: body(True))
    if edges or empties:
        pl.when((flags & (_EDGE | _EMPTY)) == 0)(lambda: body(False))
    else:
        body(False)


def _masked(s, qi, ki, q_axis, *, causal, window, block_q, block_k, seq_q,
            seq_k):
    """Score block ``s`` (queries along ``q_axis``) with the scores that do
    not count set to _NEG: the K tail padding, the q tail padding, the
    causal mask and the sliding window, each only where it can exclude
    something."""
    iota = lambda axis: jax.lax.broadcasted_iota(jnp.int32, s.shape, axis)
    qpos = qi * block_q + iota(q_axis)
    kpos = ki * block_k + iota(1 - q_axis)
    mask = None
    for on, cond in ((seq_k % block_k != 0, lambda: kpos < seq_k),
                     (seq_q % block_q != 0, lambda: qpos < seq_q),
                     (causal, lambda: qpos >= kpos),
                     (window is not None, lambda: qpos - kpos < window)):
        if on:
            mask = cond() if mask is None else jnp.logical_and(mask, cond())
    return jnp.where(mask, s, jnp.float32(_NEG))


def _flash_fwd_kernel(qb_ref, kb_ref, fl_ref, q_ref, k_ref, v_ref, o_ref,
                      lse_ref, m_scr, l_scr, acc_scr, *, scale, edges,
                      empties, **kw):
    """Grid (bh, schedule entries): each q block's k blocks in ascending
    order. The streaming-softmax stats m/l and the output accumulator live
    in VMEM scratch (persisted across the q block's entries) with
    lane-replicated (block_q, 128) stats — value carries of big f32 arrays
    through fori_loop blow Mosaic's register budget. Besides the output it
    writes each row's log-sum-exp ``m + log(l)``, lane-replicated, for the
    backward."""
    qi, ki, flags = _entry(qb_ref, kb_ref, fl_ref)
    neg = jnp.float32(_NEG)

    @pl.when((flags & _FIRST) != 0)
    def _():
        m_scr[:] = jnp.full(m_scr.shape, neg, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    def body(masked):
        q = _cast(q_ref[0], jnp.float32)                  # (block_q, d)
        k = _cast(k_ref[0], jnp.float32)                  # (block_k, d)
        v = _cast(v_ref[0], jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * jnp.float32(scale)
        if masked:
            s = _masked(s, qi, ki, 0, **kw)                # (bq, bk)
        block_k = s.shape[1]
        m_prev = m_scr[:]                                 # (bq, 128)
        l_prev = l_scr[:]
        m_curr = jnp.max(s, axis=1)[:, None]              # (bq, 1)
        m_next = jnp.maximum(m_prev, m_curr)              # (bq, 128)
        p = jnp.exp(s - _lanes_bcast(m_next, block_k))
        alpha = jnp.exp(m_prev - m_next)                  # (bq, 128)
        l_corr = alpha * l_prev
        l_next = jnp.sum(p, axis=1)[:, None] + l_corr     # (bq, 128)
        m_scr[:] = m_next
        l_scr[:] = l_next
        l_inv = jnp.where(l_next == jnp.float32(0.0),
                          jnp.float32(1.0), jnp.float32(1.0) / l_next)
        d = acc_scr.shape[-1]
        acc_scr[:] = acc_scr[:] * _lanes_bcast(l_corr * l_inv, d)
        acc_scr[:] += jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * _lanes_bcast(l_inv, d)

    _visit(body, flags, edges=edges, empties=empties)

    @pl.when((flags & _LAST) != 0)
    def _():
        o_ref[0] = _cast(acc_scr[:], o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(l_scr[:])


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, window=None):
    """q,k: [BH, T, D], v: [BH, T, DV] -> (o [BH, T, DV], lse [BH, T, 128]
    float32, each row's log-sum-exp of its scaled scores, lane-replicated).
    A head's values may be narrower or wider than its queries and keys."""
    bh, tq, d = q.shape
    tk, dv = k.shape[1], v.shape[-1]
    block_q = _clamp(block_q, tq)
    block_k = _clamp(block_k, tk)
    # pad K/V to a block multiple so every k block is full-size; the mask
    # of the tail's entries neutralises the padded keys
    nq, nk = pl.cdiv(tq, block_q), pl.cdiv(tk, block_k)
    k = _pad_to(k, nk * block_k)
    v = _pad_to(v, nk * block_k)
    kw = dict(causal=causal, window=window, block_q=block_q,
              block_k=block_k, seq_q=tq, seq_k=tk)
    q_blk, k_blk = (1, block_q, d), (1, block_k, d)
    o_blk, v_blk = (1, block_q, dv), (1, block_k, dv)
    return _sweep(
        functools.partial(_flash_fwd_kernel, scale=scale, **kw), "fwd",
        _schedule(k_major=False, **kw), nq * nk, bh,
        [(q_blk, _at_q), (k_blk, _at_k), (v_blk, _at_k)],
        [(o_blk, _at_q), ((1, block_q, _LANES), _at_q)],
        [jax.ShapeDtypeStruct((bh, tq, dv), q.dtype),
         jax.ShapeDtypeStruct((bh, tq, _LANES), jnp.float32)],
        [pltpu.VMEM((block_q, _LANES), jnp.float32),
         pltpu.VMEM((block_q, _LANES), jnp.float32),
         pltpu.VMEM((block_q, dv), jnp.float32)],
        q, k, v)


def _flash_dkv_kernel(qb_ref, kb_ref, fl_ref, q_ref, k_ref, v_ref, do_ref,
                      lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                      scale, edges, empties, **kw):
    """Grid (bh, schedule entries): each k block's q blocks in ascending
    order, dK and dV accumulating in f32 VMEM scratch. The block works on
    TRANSPOSED scores (block_k, block_q), so each query's log-sum-exp and
    delta come as one (1, block_q) row and both products are plain ones:
    dV += P^T dO, dK += dS^T q."""
    qi, ki, flags = _entry(qb_ref, kb_ref, fl_ref)

    @pl.when((flags & _FIRST) != 0)
    def _():
        dk_scr[:] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[:] = jnp.zeros(dv_scr.shape, jnp.float32)

    def body(masked):
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        st = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * jnp.float32(scale)
        if masked:
            st = _masked(st, qi, ki, 1, **kw)
        pt = jnp.exp(st - lse_ref[0])                     # (bk, bq)
        dv_scr[:] += jnp.dot(_cast(pt, do.dtype), do,
                             preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bk, bq)
        dst = pt * (dpt - delta_ref[0])
        dk_scr[:] += jnp.dot(_cast(dst, q.dtype), q,
                             preferred_element_type=jnp.float32)

    _visit(body, flags, edges=edges, empties=empties)

    @pl.when((flags & _LAST) != 0)
    def _():
        dk_ref[0] = _cast(dk_scr[:] * jnp.float32(scale), dk_ref.dtype)
        dv_ref[0] = _cast(dv_scr[:], dv_ref.dtype)


def _flash_dq_kernel(qb_ref, kb_ref, fl_ref, q_ref, k_ref, v_ref, do_ref,
                     o_ref, lse_ref, dq_ref, dq_scr, delta_scr, *, scale,
                     edges, empties, **kw):
    """Grid (bh, schedule entries): each q block's k blocks in ascending
    order, dQ accumulating in f32 VMEM scratch. lse comes lane-replicated,
    (block_q, 128), as the forward keeps its stats; the block's delta is
    formed here, in the same layout, at the q block's first entry."""
    qi, ki, flags = _entry(qb_ref, kb_ref, fl_ref)

    @pl.when((flags & _FIRST) != 0)
    def _():
        dq_scr[:] = jnp.zeros(dq_scr.shape, jnp.float32)
        delta = jnp.sum(_cast(do_ref[0], jnp.float32)
                        * _cast(o_ref[0], jnp.float32), axis=1)
        delta_scr[:] = jnp.broadcast_to(delta[:, None], delta_scr.shape)

    def body(masked):
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * jnp.float32(scale)
        if masked:
            s = _masked(s, qi, ki, 0, **kw)
        p = jnp.exp(s - _lanes_bcast(lse_ref[0], s.shape[1]))  # (bq, bk)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - _lanes_bcast(delta_scr[:], s.shape[1]))
        dq_scr[:] += jnp.dot(_cast(ds, k.dtype), k,
                             preferred_element_type=jnp.float32)

    _visit(body, flags, edges=edges, empties=empties)

    @pl.when((flags & _LAST) != 0)
    def _():
        dq_ref[0] = _cast(dq_scr[:] * jnp.float32(scale), dq_ref.dtype)


# The backward's blocks, whatever the forward's: measured on the v5e at
# [32, 4096, 64] bf16 causal (docs/architecture/note_kernels.md), 1024 x 512
# ran 4.56 ms, 512 x 512 4.72, 256 x 512 6.10; 1024 x 1024 (4.35) runs out
# of VMEM at a head width of 256 in float32, 1024 x 512 fits every width
# up to 256 in either dtype
_BWD_BLOCK_Q, _BWD_BLOCK_K = 1024, 512


def _flash_bwd(q, k, v, o, lse, do, scale, causal, block_q, block_k,
               window=None):
    """Gradients of `_flash_fwd`'s output: q,k [BH, T, D], v,o,do [BH, T,
    DV], lse its [BH, Tq, 128] -> (dq, dk, dv). Two kernels recompute each
    score block from q, k and lse and keep it in VMEM: one sweeps a k
    block's q blocks for its dK and dV, one a q block's k blocks for its
    dQ, each over the pairs its schedule lists. P and dS enter the
    products in the operands' dtype; the softmax algebra and the
    accumulators are float32."""
    bh, tq, d = q.shape
    tk, d_v = k.shape[1], v.shape[-1]
    block_q = _clamp(block_q, tq)
    block_k = _clamp(block_k, tk)
    nq, nk = pl.cdiv(tq, block_q), pl.cdiv(tk, block_k)
    # delta_i = sum_j P_ij dP_ij = dO_i . o_i, the softmax VJP's row term;
    # the dK/dV kernel takes it as rows from here, the dQ kernel forms it
    # from its own o and dO blocks (no lane-replicated copy in HBM)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    q, do, o = (_pad_to(x, nq * block_q) for x in (q, do, o))
    k, v = _pad_to(k, nk * block_k), _pad_to(v, nk * block_k)
    lse, delta = _pad_to(lse, nq * block_q), _pad_to(delta, nq * block_q)
    kw = dict(causal=causal, window=window, block_q=block_q,
              block_k=block_k, seq_q=tq, seq_k=tk)
    q_blk, k_blk = (1, block_q, d), (1, block_k, d)
    o_blk, v_blk = (1, block_q, d_v), (1, block_k, d_v)
    f32 = lambda rows, width: pltpu.VMEM((rows, width), jnp.float32)

    def row(b, i, j):
        return b, i * 0, i

    dk, dv = _sweep(
        functools.partial(_flash_dkv_kernel, scale=scale, **kw), "dkv",
        _schedule(k_major=True, **kw), nq * nk, bh,
        [(q_blk, _at_q), (k_blk, _at_k), (v_blk, _at_k), (o_blk, _at_q),
         ((1, 1, block_q), row), ((1, 1, block_q), row)],
        [(k_blk, _at_k), (v_blk, _at_k)],
        [jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype)],
        [f32(block_k, d), f32(block_k, d_v)],
        q, k, v, do, lse[:, None, :, 0], delta[:, None, :])
    dq, = _sweep(
        functools.partial(_flash_dq_kernel, scale=scale, **kw), "dq",
        _schedule(k_major=False, **kw), nq * nk, bh,
        [(q_blk, _at_q), (k_blk, _at_k), (v_blk, _at_k), (o_blk, _at_q),
         (o_blk, _at_q), ((1, block_q, _LANES), _at_q)],
        [(q_blk, _at_q)],
        [jax.ShapeDtypeStruct(q.shape, q.dtype)],
        [f32(block_q, d), pltpu.VMEM((block_q, _LANES), jnp.float32)],
        q, k, v, do, o, lse)
    return dq[:, :tq], dk[:, :tk], dv[:, :tk]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, block_q, block_k, window=None):
    return _flash_fwd(q, k, v, scale, causal, block_q, block_k, window)[0]


def _flash_vjp_fwd(q, k, v, scale, causal, block_q, block_k, window):
    o, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k, window)
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(scale, causal, block_q, block_k, window, res, g):
    return _flash_bwd(*res, g, scale, causal, _BWD_BLOCK_Q, _BWD_BLOCK_K,
                      window)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, scale=None, causal=False,
                    block_q=512, block_k=512, window=None):
    """Fused attention on [B, T, H, D] (same layout as
    `parallel.ring_attention`); k and v may have fewer heads, a divisor of
    q's (grouped-query), and v a head width of its own, DV: the output is
    [B, T, H, DV] (latent attention's 192-wide queries and keys over
    128-wide values). Differentiable: the forward and the backward are
    Pallas kernels, interpret-mode on CPU.

    ``window`` (with ``causal``) is a sliding window: query i sees the keys
    j with i - window < j <= i, itself among them; blocks wholly below it
    are not visited. None, every key up to the query.

    ``block_q`` and ``block_k`` tile the forward; the backward's tiles are
    `_BWD_BLOCK_Q` x `_BWD_BLOCK_K`. Both were measured on the v5e
    (docs/architecture/note_kernels.md). Each kernel visits the block
    pairs its `_schedule` lists, one output block's run after another —
    the streaming softmax scratch carries across a run."""
    b, tq, h, d = q.shape
    tk, hk, dv = k.shape[1], k.shape[2], v.shape[-1]
    if window is not None and (not causal or window < 1):
        raise ValueError("a window of %r keys needs causal attention and at "
                         "least one key" % (window,))
    if hk != h:
        # grouped-query: each key-value head serves h // hk query heads
        # (autodiff of the repeat sums their gradients)
        if h % hk:
            raise ValueError("%d query heads over %d key-value heads"
                             % (h, hk))
        k = jnp.repeat(k, h // hk, axis=2)
        v = jnp.repeat(v, h // hk, axis=2)
    scale = (1.0 / d ** 0.5) if scale is None else scale
    to_bh = lambda x, t: jnp.transpose(x, (0, 2, 1, 3)).reshape(
        b * h, t, x.shape[-1])
    o = _flash(to_bh(q, tq), to_bh(k, tk), to_bh(v, tk),
               scale, causal, block_q, block_k, window)
    return jnp.transpose(o.reshape(b, h, tq, dv), (0, 2, 1, 3))


# ---------------------------------------------------------------- fused LSTM

def _lstm_kernel(xp_ref, wh_ref, h0_ref, c0_ref, hseq_ref, hn_ref, cn_ref,
                 h_scr, c_scr, *, hidden):
    t = pl.program_id(0)
    nt = pl.num_programs(0)

    @pl.when(t == 0)
    def _():
        h_scr[:] = h0_ref[:]
        c_scr[:] = c0_ref[:]

    h = h_scr[:]
    gates = xp_ref[0] + jnp.dot(h, wh_ref[:],
                                preferred_element_type=jnp.float32)
    i = jax.nn.sigmoid(gates[:, :hidden])
    f = jax.nn.sigmoid(gates[:, hidden:2 * hidden])
    g = jnp.tanh(gates[:, 2 * hidden:3 * hidden])
    o = jax.nn.sigmoid(gates[:, 3 * hidden:])
    c = f * c_scr[:] + i * g
    h = o * jnp.tanh(c)
    h_scr[:] = h
    c_scr[:] = c
    hseq_ref[0] = h

    @pl.when(t == nt - 1)
    def _():
        hn_ref[:] = h
        cn_ref[:] = c


def _lstm_scan_ref(x, h0, c0, wx, wh, b):
    """lax.scan LSTM with identical math; differentiable reference used for
    the fused kernel's backward pass."""
    hid = wh.shape[0]
    xp = jnp.einsum("tbi,ih->tbh", x, wx,
                    preferred_element_type=jnp.float32) + b

    def step(carry, xpt):
        h, c = carry
        gates = xpt + jnp.dot(h, wh, preferred_element_type=jnp.float32)
        i = jax.nn.sigmoid(gates[:, :hid])
        f = jax.nn.sigmoid(gates[:, hid:2 * hid])
        g = jnp.tanh(gates[:, 2 * hid:3 * hid])
        o = jax.nn.sigmoid(gates[:, 3 * hid:])
        c = f * c + i * g
        h = o * jnp.tanh(c)
        return (h, c), h

    (hn, cn), hseq = jax.lax.scan(step, (h0, c0), xp)
    return hseq, hn, cn


@jax.custom_vjp
def fused_lstm(x, h0, c0, wx, wh, b):
    """Single-layer LSTM over a full sequence (cuDNN-RNN analog).

    x: [T, B, I]; h0/c0: [B, H]; wx: [I, 4H]; wh: [H, 4H]; b: [4H].
    Gate order i, f, g, o. Returns (h_seq [T,B,H], h_n, c_n).

    The x projection for all T timesteps runs as one MXU matmul; the Pallas
    kernel walks time on the grid keeping h/c in VMEM scratch, so HBM
    traffic per step is just the x-projection block and the h output.
    """
    t, bs, _ = x.shape
    hidden = wh.shape[0]
    xp = (jnp.einsum("tbi,ih->tbh", x, wx,
                     preferred_element_type=jnp.float32)
          + b.astype(jnp.float32))
    kern = functools.partial(_lstm_kernel, hidden=hidden)
    hseq, hn, cn = pl.pallas_call(
        kern,
        grid=(t,),
        in_specs=[
            # i*0 instead of literal 0: see the note above `_at_q`
            pl.BlockSpec((1, bs, 4 * hidden), lambda i: (i, i * 0, i * 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((hidden, 4 * hidden), lambda i: (i * 0, i * 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bs, hidden), lambda i: (i * 0, i * 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bs, hidden), lambda i: (i * 0, i * 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bs, hidden), lambda i: (i, i * 0, i * 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bs, hidden), lambda i: (i * 0, i * 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bs, hidden), lambda i: (i * 0, i * 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, bs, hidden), jnp.float32),
            jax.ShapeDtypeStruct((bs, hidden), jnp.float32),
            jax.ShapeDtypeStruct((bs, hidden), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bs, hidden), jnp.float32),
            pltpu.VMEM((bs, hidden), jnp.float32),
        ],
        interpret=_interpret(),
    )(xp, wh.astype(jnp.float32), h0.astype(jnp.float32),
      c0.astype(jnp.float32))
    return hseq.astype(x.dtype), hn.astype(x.dtype), cn.astype(x.dtype)


def _lstm_fwd_train_kernel(xp_ref, wh_ref, h0_ref, c0_ref,
                           hseq_ref, cseq_ref, gates_ref, hn_ref, cn_ref,
                           h_scr, c_scr, *, hidden):
    """Forward that ALSO saves the per-step cell states and post-activation
    gates — the residuals the fused backward consumes."""
    t = pl.program_id(0)
    nt = pl.num_programs(0)

    @pl.when(t == 0)
    def _():
        h_scr[:] = h0_ref[:]
        c_scr[:] = c0_ref[:]

    h = h_scr[:]
    gates = xp_ref[0] + jnp.dot(h, wh_ref[:],
                                preferred_element_type=jnp.float32)
    i = jax.nn.sigmoid(gates[:, :hidden])
    f = jax.nn.sigmoid(gates[:, hidden:2 * hidden])
    g = jnp.tanh(gates[:, 2 * hidden:3 * hidden])
    o = jax.nn.sigmoid(gates[:, 3 * hidden:])
    c = f * c_scr[:] + i * g
    h = o * jnp.tanh(c)
    gates_ref[0] = jnp.concatenate([i, f, g, o], axis=1)
    h_scr[:] = h
    c_scr[:] = c
    hseq_ref[0] = h
    cseq_ref[0] = c

    @pl.when(t == nt - 1)
    def _():
        hn_ref[:] = h
        cn_ref[:] = c


def _lstm_bwd_kernel(dh_seq_ref, gates_ref, cseq_ref, cprev_ref, whT_ref,
                     dhn_ref, dcn_ref, dgates_ref, dh0_ref, dc0_ref,
                     dh_scr, dc_scr, *, hidden):
    """Reverse-time recurrence of the LSTM backward. The grid walks t from
    T-1 down to 0 (reverse index maps); dh/dc carries live in VMEM scratch.
    Weight/input gradients are big sequence-wide matmuls computed OUTSIDE
    on the MXU from the dgates this kernel emits."""
    tr = pl.program_id(0)
    nt = pl.num_programs(0)

    @pl.when(tr == 0)
    def _():
        dh_scr[:] = dhn_ref[:]
        dc_scr[:] = dcn_ref[:]

    dh = dh_seq_ref[0] + dh_scr[:]
    i = gates_ref[0][:, :hidden]
    f = gates_ref[0][:, hidden:2 * hidden]
    g = gates_ref[0][:, 2 * hidden:3 * hidden]
    o = gates_ref[0][:, 3 * hidden:]
    c_t = cseq_ref[0]
    c_prev = cprev_ref[0]
    tanh_ct = jnp.tanh(c_t)
    do = dh * tanh_ct
    dc = dc_scr[:] + dh * o * (1.0 - tanh_ct * tanh_ct)
    di = dc * g
    df = dc * c_prev
    dg = dc * i
    dgates = jnp.concatenate([
        di * i * (1.0 - i),
        df * f * (1.0 - f),
        dg * (1.0 - g * g),
        do * o * (1.0 - o)], axis=1)
    dgates_ref[0] = dgates
    dh_scr[:] = jnp.dot(dgates, whT_ref[:],
                        preferred_element_type=jnp.float32)
    dc_scr[:] = dc * f

    @pl.when(tr == nt - 1)
    def _():
        dh0_ref[:] = dh_scr[:]
        dc0_ref[:] = dc_scr[:]


def _lstm_bwd_fits_vmem(bs, hidden):
    # per-step residency: 4 seq blocks (B x {H,H,H,4H}) + whT (4H x H)
    # + dgates out (B x 4H) + dh/dc scratch, all f32
    vmem = (bs * hidden * 3 + bs * 4 * hidden * 2
            + 4 * hidden * hidden + 2 * bs * hidden) * 4
    return vmem <= 10 * 1024 * 1024


def _lstm_vjp_fwd(x, h0, c0, wx, wh, b):
    t, bs, _ = x.shape
    hidden = wh.shape[0]
    if not _lstm_bwd_fits_vmem(bs, hidden):
        # large-H fallback: inference kernel forward, scan-vjp backward
        return fused_lstm(x, h0, c0, wx, wh, b), (x, h0, c0, wx, wh, b, None)
    xp = (jnp.einsum("tbi,ih->tbh", _cast(x, jnp.float32),
                     _cast(wx, jnp.float32),
                     preferred_element_type=jnp.float32)
          + b.astype(jnp.float32))
    kern = functools.partial(_lstm_fwd_train_kernel, hidden=hidden)
    hseq, cseq, gates, hn, cn = pl.pallas_call(
        kern,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, bs, 4 * hidden), lambda i: (i, i * 0, i * 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((hidden, 4 * hidden), lambda i: (i * 0, i * 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bs, hidden), lambda i: (i * 0, i * 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bs, hidden), lambda i: (i * 0, i * 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bs, hidden), lambda i: (i, i * 0, i * 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bs, hidden), lambda i: (i, i * 0, i * 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bs, 4 * hidden), lambda i: (i, i * 0, i * 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bs, hidden), lambda i: (i * 0, i * 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bs, hidden), lambda i: (i * 0, i * 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, bs, hidden), jnp.float32),
            jax.ShapeDtypeStruct((t, bs, hidden), jnp.float32),
            jax.ShapeDtypeStruct((t, bs, 4 * hidden), jnp.float32),
            jax.ShapeDtypeStruct((bs, hidden), jnp.float32),
            jax.ShapeDtypeStruct((bs, hidden), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bs, hidden), jnp.float32),
            pltpu.VMEM((bs, hidden), jnp.float32),
        ],
        interpret=_interpret(),
    )(xp, _cast(wh, jnp.float32), _cast(h0, jnp.float32),
      _cast(c0, jnp.float32))
    outs = (hseq.astype(x.dtype), hn.astype(x.dtype), cn.astype(x.dtype))
    return outs, (x, h0, c0, wx, wh, b, (hseq, cseq, gates))


def _lstm_vjp_bwd(res, g):
    x, h0, c0, wx, wh, b, saved = res
    if saved is None:
        # scan-reference fallback (same math, differentiable). f32: the
        # kernel's accumulation dtype; f64 inputs are legal at the NDArray
        # layer but not on the MXU.
        res6 = (x, h0, c0, wx, wh, b)
        res32 = tuple(_cast(r, jnp.float32) for r in res6)
        g32 = tuple(_cast(t_, jnp.float32) for t_ in g)
        _, vjp = jax.vjp(_lstm_scan_ref, *res32)
        return tuple(_cast(gr, r.dtype) for gr, r in zip(vjp(g32), res6))

    hseq, cseq, gates = saved
    t, bs, _ = x.shape
    hidden = wh.shape[0]
    dhseq, dhn, dcn = (_cast(t_, jnp.float32) for t_ in g)
    x32 = _cast(x, jnp.float32)
    h0_32 = _cast(h0, jnp.float32)
    c0_32 = _cast(c0, jnp.float32)
    cprev = jnp.concatenate([c0_32[None], cseq[:-1]], axis=0)
    hprev = jnp.concatenate([h0_32[None], hseq[:-1]], axis=0)
    whT = jnp.swapaxes(_cast(wh, jnp.float32), 0, 1)

    kern = functools.partial(_lstm_bwd_kernel, hidden=hidden)
    rev3 = lambda i: (t - 1 - i, i * 0, i * 0)
    rep2 = lambda i: (i * 0, i * 0)
    dgates, dh0, dc0 = pl.pallas_call(
        kern,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, bs, hidden), rev3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bs, 4 * hidden), rev3,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bs, hidden), rev3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bs, hidden), rev3, memory_space=pltpu.VMEM),
            pl.BlockSpec((4 * hidden, hidden), rep2,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bs, hidden), rep2, memory_space=pltpu.VMEM),
            pl.BlockSpec((bs, hidden), rep2, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bs, 4 * hidden), rev3,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bs, hidden), rep2, memory_space=pltpu.VMEM),
            pl.BlockSpec((bs, hidden), rep2, memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, bs, 4 * hidden), jnp.float32),
            jax.ShapeDtypeStruct((bs, hidden), jnp.float32),
            jax.ShapeDtypeStruct((bs, hidden), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bs, hidden), jnp.float32),
            pltpu.VMEM((bs, hidden), jnp.float32),
        ],
        interpret=_interpret(),
    )(dhseq, gates, cseq, cprev, whT, dhn, dcn)

    # sequence-wide weight/input grads: three big MXU matmuls
    wx32 = _cast(wx, jnp.float32)
    dx = jnp.einsum("tbh,ih->tbi", dgates, wx32,
                    preferred_element_type=jnp.float32)
    dwx = jnp.einsum("tbi,tbh->ih", x32, dgates,
                     preferred_element_type=jnp.float32)
    dwh = jnp.einsum("tbi,tbh->ih", hprev, dgates,
                     preferred_element_type=jnp.float32)
    db = jnp.sum(dgates, axis=(0, 1))
    grads = (dx, dh0, dc0, dwx, dwh, db)
    return tuple(_cast(gr, r.dtype)
                 for gr, r in zip(grads, (x, h0, c0, wx, wh, b)))


fused_lstm.defvjp(_lstm_vjp_fwd, _lstm_vjp_bwd)
