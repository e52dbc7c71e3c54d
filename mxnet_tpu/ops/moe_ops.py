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
computed, however the router skews. How the rows move (scope
``moe_dispatch``): ONE stable sort of the assignments by expert (those to
other devices' experts last) gives ``order``, a buffer position's
assignment, and a running count within each expert's group gives its
inverse ``rank``, an assignment's buffer position, without a second sort.
The dispatch gathers the tokens' rows into a buffer of tokens x top_k rows
by ``order``, two grouped products (`jax.lax.ragged_dot`) run over the held
experts' groups at its head, and the combine gathers each token's held
slots back by ``rank``. Both are custom VJPs whose backward is the gather
by the other permutation, so no scatter-add runs either way.

The rows past the groups: the chip's grouped kernels leave them unwritten,
forward and backward, and what is left there reached the gradient as NaN.
Masks keep them out where they could reach a value: the combine reads the
held slots only, forward and for the weights' cotangent; its backward
gives the rows past the groups exact zeros (they belong to no held slot);
the dispatch's backward reads the held slots only; and the first product's
result is masked where ReGLU reads it, which masks its cotangent too (the
second product's cotangent of its rows is unwritten past the groups). The
reference has no such layer.

Precision: the router's product is float32 at ``highest`` (the choice of
experts should not turn on a rounding), its softmax and the top-k weights
float32; the experts' products take the operands' own type and accumulate
in float32; the weighted sum of a token's experts is float32, the result
takes the expert input's type; in the backward the buffer's cotangent
takes the buffer's type, and a token's slots are summed in float32 before
taking the input's.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from .nn import _attr_num
from .registry import register

_F32 = jnp.float32
_I32 = jnp.int32


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


def permutation(key, groups):
    """The assignments' ``key`` [A] (a group in 0..groups-1) sorted stably:
    (``order`` [A], buffer position -> assignment; ``rank`` [A], its
    inverse; ``counts`` [groups]). One sort: an assignment's rank is its
    group's start plus the assignments of its group before it."""
    order = lax.sort((key, lax.iota(_I32, key.shape[0])), num_keys=1,
                     is_stable=True)[1]
    hot = (key[:, None] == jnp.arange(groups, dtype=_I32)).astype(_I32)
    counts = jnp.sum(hot, axis=0, dtype=_I32)
    place = jnp.cumsum(hot, axis=0, dtype=_I32) - 1 \
        + (jnp.cumsum(counts, dtype=_I32) - counts)
    return order, jnp.sum(hot * place, axis=1, dtype=_I32), counts


def _held_sum(rows, rank, mine, weight=None):
    """sum over k of weight[t, k] rows[rank[t, k]] where mine[t, k]: [tokens,
    d] float32. Gathered slot-major, [top_k, tokens, d], and summed slot by
    slot, so that no float32 copy of the slots is made."""
    slots = rows[rank.T.reshape(-1)].reshape(rank.shape[::-1]
                                             + rows.shape[-1:])
    total = 0.0
    for k in range(rank.shape[1]):
        slot = slots[k].astype(_F32)
        if weight is not None:
            slot = slot * weight[:, k, None]
        total = total + jnp.where(mine[:, k, None], slot, 0.0)
    return total


@jax.custom_vjp
def _dispatch(xs, order, rank, mine):
    """The buffer: row i is the token of assignment order[i]. Its backward
    gathers each token's held slots by ``rank`` [tokens, top_k] and sums
    them in float32; ``mine`` [tokens, top_k] masks the slots not held."""
    return xs[order // rank.shape[1]]


def _dispatch_fwd(xs, order, rank, mine):
    return _dispatch(xs, order, rank, mine), (rank, mine)


def _dispatch_bwd(res, g):
    rank, mine = res
    with jax.named_scope("moe_dispatch"):
        dxs = _held_sum(g, rank, mine)
    return dxs.astype(g.dtype), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(out, weight, order, rank, mine):
    """y [tokens, d] = sum over a token's held slots of weight [tokens,
    top_k] times the buffer row ``out``[rank], in float32, taking ``out``'s
    type. Its backward gathers the cotangent of the token of ``order`` for
    each buffer row once: times the slot's weight (zero past the groups) it
    is ``out``'s cotangent, and its dot with the row, read back by ``rank``
    where the slot is held, the weight's."""
    return _held_sum(out, rank, mine, weight).astype(out.dtype)


def _combine_fwd(out, weight, order, rank, mine):
    return _combine(out, weight, order, rank, mine), \
        (out, weight, order, rank, mine)


def _combine_bwd(res, dy):
    out, weight, order, rank, mine = res
    with jax.named_scope("moe_dispatch"):
        g = dy[order // rank.shape[1]].astype(_F32)
        held = jnp.where(mine, weight, 0.0).reshape(-1)[order]
        d_out = (g * held[:, None]).astype(out.dtype)
        dot = jnp.sum(out.astype(_F32) * g, axis=1)
        d_weight = jnp.where(mine, dot[rank], 0.0)
    return d_out, d_weight, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def moe(router_x, x, router_w, gate_up, down, *, first_held, top_k):
    """The held experts' part of a sparse-expert layer.

    router_x [..., d] (what the router reads), x [..., d] (what the experts
    read), router_w [experts, d], gate_up [held, 2 f, d] (gate rows, then
    up rows), down [held, d, f]. For every token, with r = softmax(router_x
    router_w^T) over all experts and S its top_k:

        w_e = r_e / sum_S r
        out = sum_{e in S, e held} w_e down_e (relu(gate_e x) * (up_e x))

    The assignments' rows move by one sort and two gathers whose backward
    is a gather too (the module's docstring): `_dispatch` into the buffer,
    `_combine` out of it.
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
    with jax.named_scope("moe_dispatch"):
        key = jnp.where(mine, local, held).reshape(-1).astype(_I32)
        order, rank, counts = permutation(key, held + 1)
        rank = rank.reshape(n, top_k)
        rows = _dispatch(xs, order, rank, mine)
    sizes = counts[:held]
    # the groups' rows; past them the first product's result is masked
    routed = (jnp.arange(n * top_k) < jnp.sum(sizes))[:, None]
    with jax.named_scope("moe_experts"):
        both = jnp.where(routed, grouped(rows, gate_up, sizes), 0)
        mixed = jax.nn.relu(both[:, :width].astype(_F32)) \
            * both[:, width:].astype(_F32)
        out = grouped(mixed.astype(xs.dtype), down, sizes)
    with jax.named_scope("moe_dispatch"):
        y = _combine(out, top, order, rank, mine)
    return y.reshape(x.shape)


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
