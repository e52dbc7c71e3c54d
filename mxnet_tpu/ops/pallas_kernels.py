"""Hand-written Pallas TPU kernels for the hot ops.

TPU-native replacement for the reference's handwritten CUDA/cuDNN kernels:
flash attention stands in for fused attention, and the fused LSTM layer
kernel replaces cuDNN's fused RNN (`src/operator/cudnn_rnn-inl.h` in the
reference). On non-TPU backends every kernel runs through the Pallas
interpreter, so the same code path is testable on CPU.

Design notes (see /opt/skills/guides/pallas_guide.md):
- flash attention: grid over (batch*heads, q blocks); K/V stay resident in
  VMEM per (batch, head) and the kernel streams q blocks, accumulating the
  numerically-stable streaming softmax in f32 registers. Causal mode bounds
  the inner k-block loop at the diagonal so masked blocks are never
  computed.
- fused LSTM: the input projection x@Wx for ALL timesteps is one big MXU
  matmul outside the kernel; the kernel walks time on the grid with h/c
  held in VMEM scratch, doing only the recurrent h@Wh matmul per step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                      scale, causal, block_q, block_k, seq_k):
    """Grid (bh, q blocks, k blocks); k innermost. The streaming-softmax
    stats m/l and the output accumulator live in VMEM scratch (persisted
    across the k sweep) with lane-replicated (block_q, 128) stats — value
    carries of big f32 arrays through fori_loop blow Mosaic's register
    budget."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    scale32 = jnp.float32(scale)
    neg = jnp.float32(_NEG)

    @pl.when(ki == 0)
    def _():
        m_scr[:] = jnp.full(m_scr.shape, neg, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    if causal:
        # skip blocks strictly above the diagonal
        run = qi * block_q + (block_q - 1) >= ki * block_k
    else:
        run = True

    @pl.when(run)
    def _():
        q = _cast(q_ref[0], jnp.float32)                  # (block_q, d)
        k = _cast(k_ref[0], jnp.float32)                  # (block_k, d)
        v = _cast(v_ref[0], jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale32  # (bq, bk)
        kpos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = kpos < seq_k                               # K/V tail padding
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = jnp.logical_and(mask, qpos >= kpos)
        s = jnp.where(mask, s, neg)

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

    @pl.when(ki == nk - 1)
    def _():
        o_ref[0] = _cast(acc_scr[:], o_ref.dtype)


def _flash_fwd(q, k, v, scale, causal, block_q, block_k):
    """q,k,v: [BH, T, D] -> [BH, T, D]."""
    bh, tq, d = q.shape
    tk = k.shape[1]

    def _clamp(block, t):
        # a block wider than the sequence is clamped to it, then rounded
        # down to a lane multiple: the in-kernel lane broadcast only
        # supports widths that are multiples of 128 (or below one lane
        # group); padding fills out the final partial block
        block = min(block, t)
        if block > _LANES:
            block = (block // _LANES) * _LANES
        return block

    block_q = _clamp(block_q, tq)
    block_k = _clamp(block_k, tk)
    # pad K/V to a block multiple so every grid block is full-size; the
    # kpos mask neutralises the padded keys
    tk_pad = pl.cdiv(tk, block_k) * block_k
    if tk_pad != tk:
        pad = [(0, 0), (0, tk_pad - tk), (0, 0)]
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    grid = (bh, pl.cdiv(tq, block_q), tk_pad // block_k)
    kern = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, seq_k=tk)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            # index maps return j*0 instead of a literal 0: the package
            # runs with jax_enable_x64 (base.py), under which a Python
            # int lowers as i64, and Mosaic cannot legalize an i64 in
            # the index map's func.return; j*0 stays i32 like the grid
            # index (tests/test_chip_compile.py compiles these for v5e)
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, j * 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, i * 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, i * 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, j * 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=_interpret(),
    )(q, k, v)


def _dense_attention(q, k, v, scale, causal, q_start=0):
    """Reference math on [BH, T, D]; used for the backward pass. The
    queries are positions ``q_start`` onward of the keys' sequence."""
    s = jnp.einsum("bqd,bkd->bqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        mask = (q_start + jnp.arange(tq))[:, None] >= jnp.arange(tk)[None, :]
        s = jnp.where(mask[None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p.astype(q.dtype), v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, scale, causal, block_q, block_k):
    return _flash_fwd(q, k, v, scale, causal, block_q, block_k)


def _flash_vjp_fwd(q, k, v, scale, causal, block_q, block_k):
    return _flash_fwd(q, k, v, scale, causal, block_q, block_k), (q, k, v)


def _flash_vjp_bwd(scale, causal, block_q, block_k, res, g):
    # backward recomputes attention with the dense math one block of
    # queries at a time: the peak is a block's scores, [BH, block_q, Tk]
    # float32, not Tq x Tk for all heads (2.1 GiB at 32 heads of 4096); the
    # keys' and values' gradients add up over the blocks in float32. A
    # pallas bwd kernel is a later optimisation
    q, k, v = res
    bh, tq, d = q.shape
    bq = block_q if tq % block_q == 0 else tq
    nb = tq // bq

    def block(carry, xs):
        qb, gb, start = xs
        _, vjp = jax.vjp(
            lambda qb, k, v: _dense_attention(qb, k, v, scale, causal, start),
            qb, k, v)
        dqb, dkb, dvb = vjp(gb)
        dk, dv = carry
        return (dk + dkb.astype(jnp.float32),
                dv + dvb.astype(jnp.float32)), dqb

    blocks = lambda x: jnp.moveaxis(x.reshape(bh, nb, bq, d), 1, 0)
    (dk, dv), dq = jax.lax.scan(
        block, (jnp.zeros(k.shape, jnp.float32),
                jnp.zeros(v.shape, jnp.float32)),
        (blocks(q), blocks(g), jnp.arange(nb, dtype=jnp.int32) * bq))
    return (jnp.moveaxis(dq, 0, 1).reshape(bh, tq, d),
            dk.astype(k.dtype), dv.astype(v.dtype))


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, scale=None, causal=False,
                    block_q=512, block_k=512):
    """Fused attention on [B, T, H, D] (same layout as
    `parallel.ring_attention`); k and v may have fewer heads, a divisor of
    q's (grouped-query). Differentiable; forward is a Pallas kernel,
    interpret-mode on CPU.

    Block defaults are measured on v5e (T=4096, d=64, causal): 512/512 runs
    ~12x faster than 128/128 (grid-invocation overhead dominates small
    blocks) and ~6x faster than XLA's dense attention, while the s-block
    (block_q x block_k f32 = 1MB) keeps ample VMEM headroom up to d=128.
    The k axis must stay the innermost sequential grid dim — the streaming
    softmax scratch carries across it."""
    b, tq, h, d = q.shape
    tk, hk = k.shape[1], k.shape[2]
    if hk != h:
        # grouped-query: each key-value head serves h // hk query heads
        # (autodiff of the repeat sums their gradients)
        if h % hk:
            raise ValueError("%d query heads over %d key-value heads"
                             % (h, hk))
        k = jnp.repeat(k, h // hk, axis=2)
        v = jnp.repeat(v, h // hk, axis=2)
    scale = (1.0 / d ** 0.5) if scale is None else scale
    to_bh = lambda x, t: jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, t, d)
    o = _flash(to_bh(q, tq), to_bh(k, tk), to_bh(v, tk),
               scale, causal, block_q, block_k)
    return jnp.transpose(o.reshape(b, h, tq, d), (0, 2, 1, 3))


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
            # i*0 instead of literal 0: see _flash_fwd index-map note
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
