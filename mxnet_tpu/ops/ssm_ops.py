"""Operators of state-space hybrid language models (Mamba-2 layers beside
attention layers): `rms_norm`, `causal_conv1d`, `mamba2_ssd`, `silu`.

The reference has none of them (its sequence stack is cudnn_rnn). Each is
plain `jax.numpy` / `lax` under autodiff and runs under a `jax.named_scope`
of its own name, so a device trace tells its fusions from the rest of the
step (an ``XLA Ops`` event carries the scope in its ``op_name``).

Precision: reductions, decays and the carried state are float32 whatever
the operands' type; the products that go to the MXU take the operands' own
type (bfloat16 in a mixed-precision model) and accumulate in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError, dtype_np
from .elemwise import _unary
from .nn import _attr_num
from .registry import register

_F32 = jnp.float32


_unary("silu", jax.nn.silu)


@register("rms_norm")
def _rms_norm(params, x, weight, *gate):
    """``x * rsqrt(mean(x^2, last axis) + eps) * weight`` in float32. With a
    third input the gated form of Mamba-2: the norm of ``x * silu(gate)``
    (the gate before the norm, one group over the whole last axis). Attrs:
    eps (1e-5), dtype (the output's; the input's by default)."""
    eps = _attr_num(params, "eps", 1e-5)
    out = params.get("dtype")
    out = x.dtype if out in (None, "None") else dtype_np(out)
    with jax.named_scope("rms_norm"):
        y = x.astype(_F32)
        if gate:
            y = y * jax.nn.silu(gate[0].astype(_F32))
        y = y * lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                          + _F32(eps))
        return ((y * weight.astype(_F32)).astype(out),)


@register("causal_conv1d")
def _causal_conv1d(params, x, weight, *bias):
    """Depthwise causal convolution over the sequence: x [B, T, C], weight
    [C, W], bias [C]; ``y[t] = sum_k weight[:, k] * x[t - (W - 1) + k]``,
    positions before a row's start read as zero."""
    width = weight.shape[1]
    with jax.named_scope("causal_conv1d"):
        t = x.shape[1]
        xp = jnp.pad(x.astype(_F32), ((0, 0), (width - 1, 0), (0, 0)))
        w = weight.astype(_F32)
        y = sum(xp[:, k:k + t, :] * w[:, k] for k in range(width))
        if bias:
            y = y + bias[0].astype(_F32)
        return (y.astype(x.dtype),)


def _carry_states(states, chunk_decay):
    """The state at the START of every chunk, [B, chunks, H, P, N] float32:
    ``S_c = chunk_decay_(c-1) * S_(c-1) + states_(c-1)``, zero at a row's
    start. ``states`` is what each chunk adds by its own end, ``chunk_decay``
    [B, chunks, H] the decay over a whole chunk."""
    def step(carry, xs):
        add, decay = xs
        return decay[..., None, None] * carry + add, carry

    _, before = lax.scan(
        step, jnp.zeros_like(states[:, 0]),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    return jnp.moveaxis(before, 0, 1)


def mamba2_ssd(x, dt, a, b, c, d, chunk):
    """The Mamba-2 recurrence, chunked (state-space duality, Dao and Gu
    2024, listing 1). For every head h, state S [P, N], zero at a row's
    start:

        S_t = exp(dt_t A) S_(t-1) + dt_t outer(x_t, B_t)
        y_t = S_t C_t + D x_t

    x [B, T, H, P]; dt [B, T, H] (after the softplus); a [H] (negative);
    b, c [B, T, G, N] with H a multiple of G; d [H]. Inside a chunk of
    ``chunk`` positions the output is the masked decay-weighted product
    ``(C B^T * L) x``; across chunks the state is carried
    (`_carry_states`). Four products go to the MXU in x's type with float32
    accumulation: C B^T, the masked product with x, the chunk states, and
    the carried state's read-out."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    if t % chunk:
        raise MXNetError("mamba2_ssd: the sequence (%d) is not a multiple of "
                         "the chunk (%d)" % (t, chunk))
    if h % g:
        raise MXNetError("mamba2_ssd: %d heads over %d groups" % (h, g))
    nc, r, kind = t // chunk, h // g, x.dtype
    xc = x.reshape(bsz, nc, chunk, g, r, p)
    bc = b.reshape(bsz, nc, chunk, g, n)
    cc = c.reshape(bsz, nc, chunk, g, n)
    # heads ahead of positions: [z, c, g, r, l]
    dtc = jnp.transpose(dt.astype(_F32).reshape(bsz, nc, chunk, g, r),
                        (0, 1, 3, 4, 2))
    # log-decay up to and including each position of its chunk
    acum = jnp.cumsum(dtc * a.astype(_F32).reshape(g, r, 1), axis=-1)

    # inside a chunk: y_i += sum_(j<=i) exp(acum_i - acum_j) dt_j (C_i.B_j) x_j
    cb = jnp.einsum("zcign,zcjgn->zcgij", cc, bc,
                    preferred_element_type=_F32)
    diff = acum[..., :, None] - acum[..., None, :]       # [z, c, g, r, i, j]
    keep = jnp.tril(jnp.ones((chunk, chunk), bool))
    m = cb[:, :, :, None] * jnp.exp(jnp.where(keep, diff, -jnp.inf)) \
        * dtc[..., None, :]
    y = jnp.einsum("zcgrij,zcjgrp->zcigrp", m.astype(kind), xc,
                   preferred_element_type=_F32)

    # what each chunk adds to the state by its own end
    to_end = jnp.exp(acum[..., -1:] - acum) * dtc          # [z, c, g, r, l]
    xw = xc.astype(_F32) * jnp.transpose(to_end, (0, 1, 4, 2, 3))[..., None]
    states = jnp.einsum("zclgrp,zclgn->zcgrpn", xw.astype(kind), bc,
                        preferred_element_type=_F32)
    before = _carry_states(
        states.reshape(bsz, nc, h, p, n),
        jnp.exp(acum[..., -1]).reshape(bsz, nc, h))
    before = before.reshape(bsz, nc, g, r, p, n)
    y = y + jnp.einsum("zclgn,zcgrpn->zclgrp", cc, before.astype(kind),
                       preferred_element_type=_F32) \
        * jnp.transpose(jnp.exp(acum), (0, 1, 4, 2, 3))[..., None]
    y = y.reshape(bsz, t, h, p) \
        + x.astype(_F32) * d.astype(_F32)[:, None]
    return y.astype(kind)


@register("mamba2_ssd")
def _mamba2_ssd(params, x, dt, a, b, c, d):
    """See `mamba2_ssd`; attr chunk_size (256). Raises on a sequence that
    is not a multiple of the chunk."""
    with jax.named_scope("mamba2_ssd"):
        return (mamba2_ssd(x, dt, a, b, c, d,
                           int(_attr_num(params, "chunk_size", 256))),)
