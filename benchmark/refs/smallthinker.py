"""The plain reference of SmallThinker's layers: forward, loss, gradients and
Adam in straightforward `jax.numpy`, float32 at ``highest``. It imports
nothing of the program and shares no code with it: attention is a masked
softmax over blocks of queries, every held expert is computed on every
token and weighted by its routing weight (zero where the router did not
choose it: no sort, no gather), Adam is written out. The one copy serves
the benchmark's cell and the program's tests (`tests/test_smallthinker.py`).

The equations (``config`` holds the keys of the published ``config.json``,
https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct). With ``h``
the residual stream [T, hidden], float32, for layer l (0-based):

    x  = RMSNorm(h; g_in[l], rms_norm_eps)
    r  = softmax(x W_r[l]^T)                  over ALL the router's experts:
                                               the router reads x, BEFORE
                                               attention
    S  = top_k(r);  w_e = r_e / sum_S r       (norm_topk_prob)
    q, k, v = x Wq, x Wk, x Wv                heads of head_dim, k and v with
                                               fewer heads (grouped-query)
    if rope_layout[l]: q, k = RoPE(q, k)      rotate-half over the whole
                                               head, theta rope_theta
    keys j of query i: i - W < j <= i          if sliding_window_layout[l]
                                               (W sliding_window_size), else
                       j <= i
    a  = softmax(q k^T / sqrt(head_dim)) v
    h  = h + a Wo^T
    y  = RMSNorm(h; g_post[l])
    h  = h + sum_{e in S, e held} w_e Wd_e(relu(Wg_e y) * (Wu_e y))   (ReGLU)

input ``h = E[ids]``; output ``logits = RMSNorm(h; g_f) W_head^T`` with a
head of its own (untied); the loss is the softmax cross-entropy of the next
token, the mean over every position.

Held experts: the configuration's ``moe_num_primary_experts`` experts from
``moe_first_held_expert``, of the router's ``moe_router_num_experts``; the
layer's result is what the held experts give, as on the one device of an
expert-parallel job that holds them.

Departures from the source, each a line of the configuration's ``assumed``:
the optimizer (Adam without decay), the initialization (`init_params`: the
source ships trained weights), sequences that start at position 0 with no
packing. Weights are stored [out, in] as `FullyConnected` takes them; an
expert stack is [held, out, in], gate rows before up rows.

Precision ``policy``: ``f32`` float32 at ``highest`` (the reference);
``bf16`` the configuration's own mixed precision written plainly (weight
and score products on bfloat16 operands with float32 accumulation, the
router float32 at ``highest``, everything else float32); ``fp8`` the
control, the nearest precision below: as ``bf16`` with every weight
product's operands rounded to float8_e4m3 and its cotangent to
float8_e5m2, and the router on bfloat16 operands.

Memory: the backward pass keeps one layer's input a layer, a block of
queries' scores a block and an expert's inputs an expert
(`jax.checkpoint`), which changes no value.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.refs.granite_hybrid import (_einsum, _product, rms_norm,
                                           seed_key)

F32 = jnp.float32


# -- shapes and the seeded generators -----------------------------------------

def layer_kinds(config):
    """[(windowed, rotary)] of the layers held."""
    n = config["num_hidden_layers"]
    return [(bool(w), bool(r)) for w, r in
            zip(config["sliding_window_layout"][:n],
                config["rope_layout"][:n])]


def param_shapes(config):
    """{parameter: shape}, written from the source's module list."""
    c = config
    hidden, head = c["hidden_size"], c["head_dim"]
    held, width = c["moe_num_primary_experts"], c["moe_ffn_hidden_size"]
    out = {"embed_weight": (c["vocab_size"], hidden)}
    for i in range(len(layer_kinds(c))):
        p = "l%d_" % i
        out[p + "input_norm_weight"] = (hidden,)
        out[p + "q_weight"] = (c["num_attention_heads"] * head, hidden)
        out[p + "k_weight"] = (c["num_key_value_heads"] * head, hidden)
        out[p + "v_weight"] = (c["num_key_value_heads"] * head, hidden)
        out[p + "o_weight"] = (hidden, c["num_attention_heads"] * head)
        out[p + "post_norm_weight"] = (hidden,)
        out[p + "router_weight"] = (c["moe_router_num_experts"], hidden)
        out[p + "experts_gate_up_weight"] = (held, 2 * width, hidden)
        out[p + "experts_down_weight"] = (held, hidden, width)
    out["final_norm_weight"] = (hidden,)
    out["head_weight"] = (c["vocab_size"], hidden)
    return out


def init_params(config, seed, init=None):
    """Every parameter from the seed, float32, in one jitted call on the
    default device: matrices, expert stacks, the embedding and the head
    normal with ``std`` (0.02); the norms' weights 1."""
    std = (init or {}).get("std", 0.02)
    shapes = param_shapes(config)

    @jax.jit
    def draw(key):
        out = {}
        for k, (name, shape) in zip(jax.random.split(key, len(shapes)),
                                    shapes.items()):
            if name.endswith("norm_weight"):
                out[name] = jnp.ones(shape, F32)
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


# -- the layers ---------------------------------------------------------------

def rotary(x, theta):
    """x [B, T, H, D]: the pair (x_i, x_(i + D/2)) at position t turned by
    t * theta^(-2i / D), in float32."""
    t, d = x.shape[1], x.shape[-1]
    freq = jnp.asarray(1.0 / theta ** (np.arange(d // 2) * 2.0 / d), F32)
    angle = jnp.arange(t, dtype=F32)[:, None] * freq[None, :]
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    first, second = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], axis=-1)


def attention(params, p, x, config, policy, windowed, rotate, block=256):
    """Masked softmax, a block of queries at a time."""
    c = config
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    bsz, t, _ = x.shape
    q = _product(policy, x, params[p + "q_weight"]).reshape(bsz, t, heads, -1)
    k = _product(policy, x, params[p + "k_weight"]).reshape(bsz, t, kv, -1)
    v = _product(policy, x, params[p + "v_weight"]).reshape(bsz, t, kv, -1)
    if rotate:
        q, k = rotary(q, c["rope_theta"]), rotary(k, c["rope_theta"])
    k = jnp.repeat(k, heads // kv, axis=2)
    v = jnp.repeat(v, heads // kv, axis=2)
    span = c["sliding_window_size"] if windowed else t
    block = block if t % block == 0 else t

    @jax.checkpoint
    def rows(at):
        qb, start = at                                  # [B, block, H, D]
        s = _einsum(policy, "bqhd,bkhd->bhqk", qb, k) * c["head_dim"] ** -0.5
        i = (start + jnp.arange(block))[:, None]
        j = jnp.arange(t)[None]
        seen = (j <= i) & (i - j < span)
        prob = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return _einsum(policy, "bhqk,bkhd->bqhd", prob, v)

    o = lax.map(rows, (jnp.moveaxis(q.reshape(bsz, t // block, block,
                                              heads, -1), 1, 0),
                       jnp.arange(t // block) * block))
    o = jnp.moveaxis(o, 0, 1).reshape(bsz, t, -1)
    return _product(policy, o, params[p + "o_weight"])


def routing(params, p, x, config, policy):
    """Each held expert's weight on each token, [B, T, held]: w_e where the
    router chose e, 0 where it did not."""
    c = config
    w = params[p + "router_weight"]
    if policy == "fp8":
        logits = jnp.matmul(x.astype(jnp.bfloat16), w.T.astype(jnp.bfloat16),
                            preferred_element_type=F32)
    else:
        logits = jnp.matmul(x, w.T, precision=lax.Precision.HIGHEST)
    top, chosen = lax.top_k(jax.nn.softmax(logits, axis=-1),
                            c["moe_num_active_primary_experts"])
    if c["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    held = c["moe_first_held_expert"] \
        + jnp.arange(c["moe_num_primary_experts"])
    picked = chosen[..., :, None] == held                # [B, T, k, held]
    return jnp.sum(jnp.where(picked, top[..., None], 0.0), axis=-2)


def experts(params, p, y, weight, config, policy):
    """sum_e weight_e * Wd_e(relu(Wg_e y) * (Wu_e y)) over the held
    experts, every expert on every token, one expert at a time."""
    width = config["moe_ffn_hidden_size"]

    @jax.checkpoint
    def one(gate_up, down, w):
        both = _product(policy, y, gate_up)
        mixed = jax.nn.relu(both[..., :width]) * both[..., width:]
        return w[..., None] * _product(policy, mixed, down)

    def add(total, at):
        return total + one(*at), None

    total, _ = lax.scan(add, jnp.zeros(y.shape, F32), (
        params[p + "experts_gate_up_weight"],
        params[p + "experts_down_weight"], jnp.moveaxis(weight, -1, 0)))
    return total


def forward(params, ids, config, policy="f32"):
    """Logits [rows, T, vocabulary], float32."""
    c = config
    eps = c["rms_norm_eps"]
    table = params["embed_weight"]
    if policy != "f32":
        table = table.astype(jnp.bfloat16)
    h = table[ids].astype(F32)
    for i, (windowed, rotate) in enumerate(layer_kinds(c)):
        p = "l%d_" % i

        @jax.checkpoint
        def layer(h, params, p=p, windowed=windowed, rotate=rotate):
            x = rms_norm(h, params[p + "input_norm_weight"], eps)
            weight = routing(params, p, x, c, policy)
            h = h + attention(params, p, x, c, policy, windowed, rotate)
            y = rms_norm(h, params[p + "post_norm_weight"], eps)
            return h + experts(params, p, y, weight, c, policy)

        h = layer(h, {n: w for n, w in params.items() if n.startswith(p)})
    x = rms_norm(h, params["final_norm_weight"], eps)
    return _product(policy, x, params["head_weight"])


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
    var, loss, softmax outputs). Adam with bias correction folded into the
    rate, no decay:

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
        del probs
    return {"losses": [float(v) for v in losses], "first_mean": first_mean,
            "first_probs": first_probs, "params": params, "mean": mean,
            "var": var}
