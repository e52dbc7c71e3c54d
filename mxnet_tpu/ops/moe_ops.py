"""Operators of sparse-expert layers: `moe`.

A layer of routed gated experts (ReGLU or SwiGLU) of which this device
holds a contiguous run, ``first_held`` to ``first_held + held - 1``
(``held`` is the stacked weights' first axis), of the router's experts (the
router matrix's first axis): expert parallelism's share of the layer,
without the exchange between the devices. The router scores every token
over ALL the experts and sends it to its ``top_k``, their weights
renormalised to sum to one; the layer computes the part of the result that
its held experts give, for exactly the (token, expert) pairs routed to
them. What the other experts would add is left out.

Two routers (``score``), the rest of the layer shared:

- ``softmax``: the top_k of the softmax over the experts, renormalised.
- ``sigmoid`` (DeepSeek-V3's, arXiv:2412.19437 section 2.1.2): the scores
  are sigmoids; the top_k is chosen on the score plus a selection bias
  [experts] that only selects, and the weights are the chosen scores,
  renormalised. The bias is an auxiliary state, moved after each step by
  ``bias_rate`` times the sign of (the mean load - the expert's load),
  the load being the assignments of the step's tokens, and takes no
  gradient. A sequence-wise balance loss, ``balance_alpha`` times
  ``mean over rows of sum_e f_e P_e`` (f_e: experts / (top_k T) times the
  assignments to e of a row's T tokens, a count; P_e: the mean over the
  row of e's share of a token's scores), rides the router's gradient as
  `IdentityAttachKLSparseReg` attaches its penalty: identity forward, its
  gradient added to the logits' cotangent. The loss is not an output.

Either router's weights are then scaled by ``routed_scale``.

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
experts should not turn on a rounding), its scores, the top-k weights and
the balance loss float32; the experts' products take the operands' own
type and accumulate in float32; the weighted sum of a token's experts is
float32, the result takes the expert input's type; in the backward the
buffer's cotangent takes the buffer's type, and a token's slots are summed
in float32 before taking the input's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from .nn import _attr_num
from .registry import register

_ACTS = {"relu": jax.nn.relu, "silu": jax.nn.silu}

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


def _softmax_route(logits, top_k):
    """(weights [n, top_k], experts [n, top_k]): the top_k of the softmax,
    renormalised to sum to one."""
    top, expert = lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    return top / jnp.sum(top, axis=-1, keepdims=True), expert


def _router_logits(router_x, router_w):
    """[tokens, experts], float32 at ``highest``."""
    return jnp.matmul(router_x.reshape(-1, router_x.shape[-1]).astype(_F32),
                      router_w.astype(_F32).T,
                      precision=lax.Precision.HIGHEST)


def _sigmoid_route(logits, bias, top_k, bias_rate, balance_alpha, t):
    """(weights [n, top_k], experts [n, top_k], the moved bias) of sigmoid
    routing (the module's docstring); ``t`` tokens to a row of the balance
    loss."""
    experts = logits.shape[-1]
    rows = logits.reshape(-1, t, experts)
    expert = lax.top_k(jax.nn.sigmoid(logits) + bias.astype(_F32), top_k)[1]
    hot = jnp.sum(expert.reshape(-1, t, top_k, 1) == jnp.arange(experts),
                  axis=(1, 2), dtype=_I32)                # [rows, experts]
    load = jnp.sum(hot, axis=0).astype(_F32)
    moved = bias + (_F32(bias_rate) * jnp.sign(
        jnp.mean(load) - load)).astype(bias.dtype)
    if balance_alpha:
        rows = _balanced(rows, hot, top_k, balance_alpha)
    top = jnp.take_along_axis(jax.nn.sigmoid(rows).reshape(logits.shape),
                              expert, axis=-1)
    return top / jnp.sum(top, axis=-1, keepdims=True), expert, moved


def _balance_loss(logits, counts, top_k):
    """The sequence-wise balance loss of sigmoid routing: logits [rows, T,
    experts], counts [rows, experts] the assignments of each row's tokens.
    ``mean over rows of sum_e f_e P_e``, f_e = experts / (top_k T)
    counts_e, P_e the row's mean of e's share of each token's scores."""
    experts, t = logits.shape[-1], logits.shape[-2]
    scores = jax.nn.sigmoid(logits)
    share = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True),
                     axis=-2)
    f = counts.astype(_F32) * (experts / (top_k * t))
    return jnp.mean(jnp.sum(f * share, axis=-1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _balanced(logits, counts, top_k, alpha):
    """``logits`` [rows, T, experts] as they are; their cotangent gains
    ``alpha`` times the gradient of `_balance_loss`."""
    return logits


def _balanced_fwd(logits, counts, top_k, alpha):
    return logits, (logits, counts)


def _balanced_bwd(top_k, alpha, res, g):
    logits, counts = res
    extra = jax.grad(_balance_loss)(logits, counts, top_k)
    return g + _F32(alpha) * extra, None


_balanced.defvjp(_balanced_fwd, _balanced_bwd)


def moe(router_x, x, router_w, gate_up, down, bias=None, *, first_held,
        top_k, score="softmax", routed_scale=1.0, act="relu", bias_rate=0.0,
        balance_alpha=0.0):
    """The held experts' part of a sparse-expert layer.

    router_x [..., d] (what the router reads), x [..., d] (what the experts
    read), router_w [experts, d], gate_up [held, 2 f, d] (gate rows, then
    up rows), down [held, d, f]; ``bias`` [experts], the selection bias,
    with ``score="sigmoid"`` only. For every token, with w_e its routing
    weight (the module's docstring) on the experts S chosen:

        out = routed_scale sum_{e in S, e held} w_e down_e(act(gate_e x)
                                                          * (up_e x))

    ``act`` relu (ReGLU) or silu (SwiGLU). The assignments' rows move by
    one sort and two gathers whose backward is a gather too (the module's
    docstring): `_dispatch` into the buffer, `_combine` out of it.
    Returns out, or (out, the moved bias) under ``sigmoid``.
    """
    held, width = gate_up.shape[0], gate_up.shape[1] // 2
    dim = x.shape[-1]
    xs = x.reshape(-1, dim)
    n = xs.shape[0]
    if score == "softmax":
        top, expert = _softmax_route(_router_logits(router_x, router_w),
                                     top_k)
    else:
        with jax.named_scope("moe_router"):
            top, expert, moved = _sigmoid_route(
                _router_logits(router_x, router_w), bias, top_k, bias_rate,
                balance_alpha, x.shape[-2] if x.ndim > 2 else n)
    if routed_scale != 1.0:
        top = top * _F32(routed_scale)
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
        mixed = _ACTS[act](both[:, :width].astype(_F32)) \
            * both[:, width:].astype(_F32)
        out = grouped(mixed.astype(xs.dtype), down, sizes)
    with jax.named_scope("moe_dispatch"):
        y = _combine(out, top, order, rank, mine).reshape(x.shape)
    return y if score == "softmax" else (y, moved)


@register("moe", mutate_aux=(5,), need_train_flag=True)
def _moe(params, router_x, x, router_w, gate_up, down, *bias):
    """See `moe`. Attrs: first_held (0), top_k, score (softmax | sigmoid),
    routed_scale (1), act (relu | silu), bias_rate (0), balance_alpha (0).
    Under ``sigmoid`` a sixth input, the selection bias [experts], an
    auxiliary state that a training step moves (`moe`)."""
    experts, held = router_w.shape[0], gate_up.shape[0]
    first = int(_attr_num(params, "first_held", 0))
    top_k = int(_attr_num(params, "top_k", 1))
    score = params.get("score", "softmax")
    act = params.get("act", "relu")
    if not 0 <= first <= experts - held:
        raise MXNetError("moe: a router over %d experts, %d held from %d"
                         % (experts, held, first))
    if not 0 < top_k <= experts:
        raise MXNetError("moe: top_k %d of %d experts" % (top_k, experts))
    if score not in ("softmax", "sigmoid") or act not in _ACTS:
        raise MXNetError("moe: score %r, act %r" % (score, act))
    if (score == "sigmoid") != bool(bias):
        raise MXNetError("moe: a selection bias goes with score='sigmoid' "
                         "and only with it")
    with jax.named_scope("moe"):
        out = moe(router_x, x, router_w, gate_up, down, *bias,
                  first_held=first, top_k=top_k, score=score,
                  routed_scale=float(_attr_num(params, "routed_scale", 1.0)),
                  act=act, bias_rate=float(_attr_num(params, "bias_rate", 0)),
                  balance_alpha=float(_attr_num(params, "balance_alpha", 0)))
    if score == "softmax":
        return (out,)
    y, moved = out
    return y, (moved if params.get("_is_train") else bias[0])
