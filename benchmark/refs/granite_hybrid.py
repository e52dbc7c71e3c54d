"""The plain reference of Granite-4.0-H's layers: forward, loss, gradients
and Adam in straightforward `jax.numpy`, float32 at ``highest``. It imports
nothing of the program and shares no code with it: the recurrence runs
position by position (never in chunks), attention is a dense masked
softmax, Adam is written out. The one copy serves the benchmark's cell and
the program's tests (`tests/test_granite_hybrid.py`).

The equations (``config`` holds the keys of the published ``config.json``,
https://huggingface.co/ibm-granite/granite-4.0-h-micro, `model_type`
``granitemoehybrid``). With ``h`` the residual stream [T, hidden], per layer

    h = h + residual_multiplier * mixer(RMSNorm(h))
    h = h + residual_multiplier * mlp(RMSNorm(h))
    RMSNorm(x) = x * rsqrt(mean(x^2) + rms_norm_eps) * w

input ``h = embedding_multiplier * E[ids]``; output ``logits = RMSNorm(h) @
E^T / logits_scaling`` with the same ``E``; the loss is the softmax
cross-entropy of the next token, the mean over every position that has one.

  mlp        gate, up = split(x @ W_in); (silu(gate) * up) @ W_out; no bias
  attention  q heads and fewer k, v heads of hidden / heads, no bias, NO
             rotary or other position term ("nope"), causal,
             softmax(q k^T * attention_multiplier) v, then W_o
  mamba      z, xBC, dt = split(x @ W_in, [inner, inner + 2 G N, heads]);
             xBC = silu(causal depthwise conv1d(xBC, width d_conv, bias));
             x, B, C = split(xBC, [inner, G N, G N]); x as heads of d_head;
             B, C shared by the heads of a group;
             dt = softplus(dt + dt_bias); A = -exp(A_log); per head, state
             S [d_head, N], zero at a row's start:
                 S_t = exp(dt_t A) S_(t-1) + dt_t outer(x_t, B_t)
                 y_t = S_t C_t + D x_t
             y = RMSNorm_inner(y * silu(z)) (the gate BEFORE the norm, one
             group over all of inner); y @ W_out

Departures from the source, each a line of the configuration's ``assumed``:
the optimizer (Adam without decay; the source states none), the
initialization (`init_params`; the source ships trained weights),
sequences that start at position 0 with no packing. Weights are stored [out, in] as `FullyConnected` takes them; the
depthwise filter is [channels, width].

Precision ``policy``: ``f32`` float32 at ``highest`` (the reference);
``bf16`` the configuration's own mixed precision written plainly (matrix
operands bfloat16, float32 accumulation, everything else float32); ``fp8``
the control, the nearest precision below: as ``bf16`` with every weight
product's operands rounded to float8_e4m3 (at the tensor's largest
magnitude) and its cotangent to float8_e5m2.

Memory: the backward pass keeps one layer's input a layer, a block of
positions' state a block and a block of queries' scores a block
(`jax.checkpoint`), which changes no value: 4096 positions of 64 states of
64 x 128 would otherwise be 8.6 GB a layer.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32


# -- shapes and the seeded generators -----------------------------------------

def layer_types(config):
    return list(config["layer_types"][:config["num_hidden_layers"]])


def param_shapes(config):
    """{parameter: shape}, written from the source's module list."""
    c = config
    hidden, wide = c["hidden_size"], c["shared_intermediate_size"]
    head = hidden // c["num_attention_heads"]
    inner = c["mamba_n_heads"] * c["mamba_d_head"]
    xbc = inner + 2 * c["mamba_n_groups"] * c["mamba_d_state"]
    out = {"embed_weight": (c["vocab_size"], hidden)}
    for i, kind in enumerate(layer_types(c)):
        p = "l%d_" % i
        out[p + "norm1_weight"] = (hidden,)
        if kind == "mamba":
            out[p + "in_proj_weight"] = (inner + xbc + c["mamba_n_heads"],
                                         hidden)
            out[p + "conv_weight"] = (xbc, c["mamba_d_conv"])
            out[p + "conv_bias"] = (xbc,)
            for name in ("dt_bias", "A_log", "D"):
                out[p + name] = (c["mamba_n_heads"],)
            out[p + "mixer_norm_weight"] = (inner,)
            out[p + "out_proj_weight"] = (hidden, inner)
        else:
            out[p + "q_weight"] = (c["num_attention_heads"] * head, hidden)
            out[p + "k_weight"] = (c["num_key_value_heads"] * head, hidden)
            out[p + "v_weight"] = (c["num_key_value_heads"] * head, hidden)
            out[p + "o_weight"] = (hidden, c["num_attention_heads"] * head)
        out[p + "norm2_weight"] = (hidden,)
        out[p + "mlp_in_weight"] = (2 * wide, hidden)
        out[p + "mlp_out_weight"] = (hidden, wide)
    out["final_norm_weight"] = (hidden,)
    return out


def seed_key(seed, stream):
    """A PRNG key from any whole number (seeds pass 2**31) and a stream."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x3FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 30) & 0x3FFFFFFF)
    return jax.random.fold_in(key, stream)


def init_params(config, seed, init=None):
    """Every parameter from the seed, float32, in one jitted call on the
    default device, as the Mamba-2 authors' code draws them: matrices and
    the embedding normal with ``std`` (0.02); the depthwise filter and its
    bias uniform in +-``conv_bound`` (1 / sqrt(d_conv) = 0.5, what their
    `nn.Conv1d` is left with: a filter of 0.02 would leave B, C and the
    state a thousand times under the skip term D x, and the recurrence out
    of every number compared); ``A_log = log(uniform(1, 16))``;
    ``dt_bias`` the inverse softplus of a log-uniform step in (``dt_min``,
    ``dt_max``) (0.001, 0.1); ``D`` and the norms' weights 1."""
    init = init or {}
    std = init.get("std", 0.02)
    dt_min, dt_max = init.get("dt_min", 1e-3), init.get("dt_max", 0.1)
    bound = init.get("conv_bound", config["mamba_d_conv"] ** -0.5)
    shapes = param_shapes(config)

    @jax.jit
    def draw(key):
        out = {}
        for k, (name, shape) in zip(jax.random.split(key, len(shapes)),
                                    shapes.items()):
            if name.endswith("A_log"):
                out[name] = jnp.log(jax.random.uniform(k, shape, F32, 1., 16.))
            elif name.endswith("dt_bias"):
                dt = jnp.exp(jax.random.uniform(
                    k, shape, F32, np.log(dt_min), np.log(dt_max)))
                out[name] = dt + jnp.log(-jnp.expm1(-dt))
            elif name.endswith("norm_weight") or name.endswith("norm1_weight") \
                    or name.endswith("norm2_weight") or name.endswith("_D"):
                out[name] = jnp.ones(shape, F32)
            elif name.endswith("conv_weight") or name.endswith("conv_bias"):
                out[name] = jax.random.uniform(k, shape, F32, -bound, bound)
            else:
                out[name] = np.float32(std) * jax.random.normal(k, shape, F32)
        return out

    return draw(seed_key(seed, 1))


def make_pool(config, seed, batches, rows, tokens):
    """``batches`` seeded batches of ids, uniform over the vocabulary:
    (ids [batches, rows, tokens] int32, labels the same shape float32, the
    NEXT id of every position; a row draws tokens + 1 ids so that its last
    position has one too)."""
    drawn = jax.random.randint(seed_key(seed, 2), (batches, rows, tokens + 1),
                               0, config["vocab_size"], jnp.int32)
    return drawn[..., :-1], drawn[..., 1:].astype(F32)


# -- precision ----------------------------------------------------------------

def _fp8_round(x, dtype):
    x32 = x.astype(F32)
    scale = jnp.maximum(jnp.max(jnp.abs(x32)), 1e-30) \
        / float(jnp.finfo(dtype).max)
    return ((x32 / scale).astype(dtype).astype(F32) * scale).astype(x.dtype)


@jax.custom_vjp
def _fp8_operand(x):
    return _fp8_round(x, jnp.float8_e4m3fn)


_fp8_operand.defvjp(lambda x: (_fp8_operand(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _fp8_cotangent(y):
    return y


_fp8_cotangent.defvjp(lambda y: (y, None),
                      lambda _, g: (_fp8_round(g, jnp.float8_e5m2),))


def _product(policy, x, w):
    """``x @ w^T`` with a weight stored [out, in]; float32 out."""
    if policy == "f32":
        return jnp.matmul(x, w.T, precision=lax.Precision.HIGHEST)
    x, w = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    if policy == "fp8":
        x, w = _fp8_operand(x), _fp8_operand(w)
    y = jnp.matmul(x, w.T, preferred_element_type=F32)
    return _fp8_cotangent(y) if policy == "fp8" else y


def _einsum(policy, spec, a, b):
    """A product between activations (scores, read-outs): float32 at
    ``highest``, or bfloat16 operands with float32 accumulation."""
    if policy == "f32":
        return jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST)
    return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=F32)


# -- the layers ---------------------------------------------------------------

def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def causal_conv1d(x, w, bias):
    """x [B, T, C], w [C, W]: y_t = sum_k w[:, k] x_(t - (W - 1) + k) + b."""
    width, t = w.shape[1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(xp[:, k:k + t] * w[:, k] for k in range(width)) + bias


def recurrence(x, dt, a, b, c, d, block=64):
    """The state-space recurrence position by position. x [B, T, H, P], dt
    [B, T, H], a [H], b and c [B, T, H, N] (already given to every head),
    d [H]. The backward pass keeps the state at every ``block`` positions
    and recomputes between."""
    bsz, t, h, p = x.shape

    def step(state, at):
        xt, dtt, bt, ct = at
        state = jnp.exp(dtt * a)[..., None, None] * state \
            + (dtt[..., None] * xt)[..., :, None] * bt[..., None, :]
        return state, jnp.sum(state * ct[..., None, :], axis=-1) \
            + d[:, None] * xt

    @jax.checkpoint
    def run_block(state, at):
        return lax.scan(step, state, at)

    block = block if t % block == 0 else t
    time_first = [jnp.moveaxis(v, 1, 0).reshape(
        (t // block, block) + v.shape[:1] + v.shape[2:])
        for v in (x, dt, b, c)]
    _, y = lax.scan(run_block, jnp.zeros((bsz, h, p, b.shape[-1]), F32),
                    tuple(time_first))
    return jnp.moveaxis(y.reshape((t,) + y.shape[2:]), 0, 1)


def mamba_mixer(params, p, x, config, policy):
    c = config
    heads, head = c["mamba_n_heads"], c["mamba_d_head"]
    groups, state = c["mamba_n_groups"], c["mamba_d_state"]
    inner, gn = heads * head, groups * state
    proj = _product(policy, x, params[p + "in_proj_weight"])
    z, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * gn], axis=-1)
    xbc = jax.nn.silu(causal_conv1d(xbc, params[p + "conv_weight"],
                                    params[p + "conv_bias"]))
    xs, b, cc = jnp.split(xbc, [inner, inner + gn], axis=-1)
    lead = xs.shape[:2]
    to_heads = lambda v: jnp.repeat(v.reshape(lead + (groups, state)),
                                    heads // groups, axis=2)
    y = recurrence(xs.reshape(lead + (heads, head)),
                   jax.nn.softplus(dt + params[p + "dt_bias"]),
                   -jnp.exp(params[p + "A_log"]), to_heads(b), to_heads(cc),
                   params[p + "D"])
    y = rms_norm(y.reshape(lead + (inner,)) * jax.nn.silu(z),
                 params[p + "mixer_norm_weight"], c["rms_norm_eps"])
    return _product(policy, y, params[p + "out_proj_weight"])


def attention(params, p, x, config, policy, block=512):
    """Dense masked softmax, a block of queries at a time."""
    c = config
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    bsz, t, _ = x.shape
    q = _product(policy, x, params[p + "q_weight"]).reshape(bsz, t, heads, -1)
    k = _product(policy, x, params[p + "k_weight"]).reshape(bsz, t, kv, -1)
    v = _product(policy, x, params[p + "v_weight"]).reshape(bsz, t, kv, -1)
    k = jnp.repeat(k, heads // kv, axis=2)
    v = jnp.repeat(v, heads // kv, axis=2)
    block = block if t % block == 0 else t

    @jax.checkpoint
    def rows(at):
        qb, start = at                                  # [B, block, H, D]
        s = _einsum(policy, "bqhd,bkhd->bhqk", qb, k) \
            * c["attention_multiplier"]
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(t)[None]
        prob = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return _einsum(policy, "bhqk,bkhd->bqhd", prob, v)

    o = lax.map(rows, (jnp.moveaxis(q.reshape(bsz, t // block, block,
                                              heads, -1), 1, 0),
                       jnp.arange(t // block) * block))
    o = jnp.moveaxis(o, 0, 1).reshape(bsz, t, -1)
    return _product(policy, o, params[p + "o_weight"])


def mlp(params, p, x, policy):
    gate, up = jnp.split(_product(policy, x, params[p + "mlp_in_weight"]),
                         2, axis=-1)
    return _product(policy, jax.nn.silu(gate) * up,
                    params[p + "mlp_out_weight"])


def forward(params, ids, config, policy="f32"):
    """Logits [rows, T, vocabulary], float32."""
    c = config
    eps, mult = c["rms_norm_eps"], c["residual_multiplier"]
    table = params["embed_weight"]
    if policy != "f32":
        table = table.astype(jnp.bfloat16)
    h = c["embedding_multiplier"] * table[ids].astype(F32)
    for i, kind in enumerate(layer_types(c)):
        p = "l%d_" % i

        @jax.checkpoint
        def layer(h, params, p=p, kind=kind):
            x = rms_norm(h, params[p + "norm1_weight"], eps)
            if kind == "mamba":
                x = mamba_mixer(params, p, x, c, policy)
            else:
                x = attention(params, p, x, c, policy)
            h = h + mult * x
            x = rms_norm(h, params[p + "norm2_weight"], eps)
            return h + mult * mlp(params, p, x, policy)

        h = layer(h, {n: w for n, w in params.items() if n.startswith(p)})
    x = rms_norm(h, params["final_norm_weight"], eps)
    return _product(policy, x, params["embed_weight"]) / c["logits_scaling"]


def loss_fn(params, ids, labels, config, policy="f32"):
    """(mean cross-entropy over every position, softmax outputs [rows x T,
    vocabulary]), float32."""
    logits = forward(params, ids, config, policy)
    logp = jax.nn.log_softmax(logits.reshape(-1, logits.shape[-1]), axis=-1)
    picked = jnp.take_along_axis(
        logp, labels.reshape(-1).astype(jnp.int32)[:, None], axis=1)
    return -jnp.mean(picked), jnp.exp(logp)


# -- Adam, as the configuration states it -------------------------------------

def make_step(config, optimizer, policy="f32"):
    """One jitted step: (params, mean, var, t, ids, labels) -> (params, mean,
    var, loss, softmax outputs, first gradient's norms). Adam with bias
    correction folded into the rate, no decay:

        m = b1 m + (1 - b1) g;  v = b2 v + (1 - b2) g^2
        w = w - lr sqrt(1 - b2^t) / (1 - b1^t) * m / (sqrt(v) + eps)
    """
    lr, b1, b2, eps = (np.float32(optimizer[k]) for k in
                       ("learning_rate", "beta1", "beta2", "epsilon"))
    grad = jax.value_and_grad(
        functools.partial(loss_fn, config=config, policy=policy),
        has_aux=True)

    # mxanalyze: allow(donation-hazard): the reference imports nothing of the program, its donation policy included; 9 GB of float32 state are written over in place on the chip, and a CPU run ignores the request
    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, mean, var, t, ids, labels):
        (loss, probs), grads = grad(params, ids, labels)
        rate = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        new_p, new_m, new_v = {}, {}, {}
        # mxanalyze: allow(dispatch-amplification): Adam written out leaf by leaf, as a plain reference does; the leaves have different shapes
        for name, w in params.items():
            g = grads[name]
            new_m[name] = b1 * mean[name] + (1 - b1) * g
            new_v[name] = b2 * var[name] + (1 - b2) * g * g
            new_p[name] = w - rate * new_m[name] / (jnp.sqrt(new_v[name])
                                                    + eps)
        return new_p, new_m, new_v, loss, probs

    return step


def follow(config, optimizer, params, batches, policy="f32"):
    """Drive the reference through ``batches`` [(ids, labels), ...] from
    ``params`` (which it consumes). Returns each step's loss, the first
    step's softmax outputs, Adam's first moment after step 1 (``first_mean``
    = (1 - beta1) g, from which the first gradient follows), both moments
    and the parameters at the end."""
    step = make_step(config, optimizer, policy)
    mean = {n: jnp.zeros_like(w) for n, w in params.items()}
    var = {n: jnp.zeros_like(w) for n, w in params.items()}
    losses, first_mean, first_probs = [], None, None
    for i, (ids, labels) in enumerate(batches):
        params, mean, var, loss, probs = step(
            params, mean, var, np.float32(i + 1), ids, labels)
        losses.append(loss)
        if i == 0:
            first_mean = {n: np.asarray(v) for n, v in mean.items()}
            first_probs = np.asarray(probs)
    return {"losses": [float(v) for v in losses], "first_mean": first_mean,
            "first_probs": first_probs, "params": params, "mean": mean,
            "var": var}
