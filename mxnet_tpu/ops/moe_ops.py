"""Operators of sparse-expert layers: `moe`.

A layer of routed ReGLU experts of which this device holds a contiguous run,
``first_held`` to ``first_held + held - 1`` (``held`` is the stacked
weights' first axis), of the router's experts (the router matrix's first
axis): expert parallelism's share of the layer, without the exchange between
the devices. The router scores every token over ALL the experts and sends
it to its ``top_k``, their weights renormalised to sum to one; the layer
computes the part of the result that its held experts give, for exactly the
(token, expert) pairs routed to them. What the other experts would add is
left out.

Dropless: there is no capacity. Every assignment to a held expert is
computed, however the router skews. The assignments are sorted by expert
(those to other devices' experts last) into a buffer of tokens x top_k rows,
and two grouped products (`jax.lax.ragged_dot`) run over the held experts'
groups at its head. The rows past the groups are set to zero here, forward
and backward: the chip's grouped kernels leave them unwritten, and what is
left there reached the gradient as NaN. The reference has no such layer.

Precision: the router's product is float32 at ``highest`` (the choice of
experts should not turn on a rounding), its softmax and the top-k weights
float32; the experts' products take the operands' own type and accumulate
in float32; the weighted sum of a token's experts is float32, the result
takes the expert input's type.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from .nn import _attr_num
from .registry import register

_F32 = jnp.float32


def grouped(rows, weights, sizes):
    """``rows`` [R, in] grouped by ``sizes`` [experts] (the first sizes[0]
    rows to expert 0, ...), each group times its expert's ``weights``
    [experts, out, in] transposed. Rows past the groups are undefined.

    The stack enters `jax.lax.ragged_dot` as [experts, in, out], the
    layout that function documents. Handed [experts, out, in] with its
    last axis contracted, XLA's grouped kernel agreed with per-expert
    products when compiled alone, but inside the compiled step on a v5e
    the layer's results were wrong: the model's first softmax outputs
    read 0.104 from the float32 reference, and 0.0117 in this layout."""
    return lax.ragged_dot(rows, jnp.swapaxes(weights, 1, 2), sizes,
                          preferred_element_type=rows.dtype)


def moe(router_x, x, router_w, gate_up, down, *, first_held, top_k):
    """The held experts' part of a sparse-expert layer.

    router_x [..., d] (what the router reads), x [..., d] (what the experts
    read), router_w [experts, d], gate_up [held, 2 f, d] (gate rows, then
    up rows), down [held, d, f]. For every token, with r = softmax(router_x
    router_w^T) over all experts and S its top_k:

        w_e = r_e / sum_S r
        out = sum_{e in S, e held} w_e down_e (relu(gate_e x) * (up_e x))
    """
    held, width = gate_up.shape[0], gate_up.shape[1] // 2
    dim = x.shape[-1]
    xs = x.reshape(-1, dim)
    n = xs.shape[0]
    logits = jnp.matmul(router_x.reshape(-1, dim).astype(_F32),
                        router_w.astype(_F32).T,
                        precision=lax.Precision.HIGHEST)
    top, expert = lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    local = expert - first_held
    mine = (local >= 0) & (local < held)
    key = jnp.where(mine, local, held).reshape(-1).astype(jnp.int32)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(held, dtype=jnp.int32),
                    axis=0, dtype=jnp.int32)
    # the sorted assignments to held experts come first; the rest, and
    # their cotangents, are zeros (`where` masks both ways)
    routed = (jnp.arange(n * top_k) < jnp.sum(sizes))[:, None]
    rows = jnp.where(routed, xs[order // top_k], 0)
    with jax.named_scope("moe_experts"):
        both = jnp.where(routed, grouped(rows, gate_up, sizes), 0)
        mixed = jax.nn.relu(both[:, :width].astype(_F32)) \
            * both[:, width:].astype(_F32)
        out = jnp.where(routed, grouped(mixed.astype(xs.dtype), down, sizes),
                        0)
    slots = out[jnp.argsort(order)].reshape(n, top_k, dim)
    weight = jnp.where(mine, top, 0.0)
    y = jnp.sum(slots.astype(_F32) * weight[..., None], axis=1)
    return y.astype(x.dtype).reshape(x.shape)


@register("moe")
def _moe(params, router_x, x, router_w, gate_up, down):
    """See `moe`. Attrs: first_held (0), top_k."""
    experts, held = router_w.shape[0], gate_up.shape[0]
    first = int(_attr_num(params, "first_held", 0))
    top_k = int(_attr_num(params, "top_k", 1))
    if not 0 <= first <= experts - held:
        raise MXNetError("moe: a router over %d experts, %d held from %d"
                         % (experts, held, first))
    if not 0 < top_k <= experts:
        raise MXNetError("moe: top_k %d of %d experts" % (top_k, experts))
    with jax.named_scope("moe"):
        return (moe(router_x, x, router_w, gate_up, down, first_held=first,
                    top_k=top_k),)
