"""The plain reference of Moonlight's layers: forward, loss, gradients, the
routers' selection biases and Adam in straightforward `jax.numpy`, float32
at ``highest``. It imports nothing of the program and shares no code with
it: attention is a masked softmax over blocks of queries, every held
expert is computed on every token and weighted by its routing weight (zero
where the router did not choose it: no sort, no gather), the balance loss
is a term of the loss that `jax.grad` differentiates, Adam is written out.
The one copy serves the benchmark's cell and the program's tests
(`tests/test_moonlight.py`).

The equations (``config`` holds the keys of the published ``config.json``,
https://huggingface.co/moonshotai/Moonlight-16B-A3B, ``model_type``
``deepseek_v3``, and the sizes it leaves open, each a line of the
configuration's ``assumed``). With ``h`` the residual stream [T, hidden],
float32, positions t from 0 at a row's start, for layer l (0-based):

    x   = RMSNorm(h; g_in[l], rms_norm_eps)
    q   = x Wq[l]                 [T, heads, nope + rope] = [q_nope | q_pe]
    ckv, k_pe = split(x Wkva[l], kv_lora_rank, rope)   k_pe one head, shared
    ckv = RMSNorm(ckv; g_kv[l], kv_norm_eps)
    k_nope, v = split(ckv Wkvb[l], nope, v_head_dim per head)
    q_pe, k_pe = RoPE(q_pe), RoPE(k_pe)     rotate-half over the rope dims,
                                            theta rope_theta
    k   = [k_nope | k_pe repeated over the heads]
    a   = softmax(q k^T / sqrt(nope + rope), causal) v      [T, heads, v]
    h   = h + a Wo[l]
    y   = RMSNorm(h; g_post[l], rms_norm_eps)
    l < first_k_dense_replace:
      h = h + Wd(silu(Wg y) * (Wu y))                  width intermediate_size
    else:
      s   = sigmoid(y Wr[l]^T)             over ALL the router's experts
      S   = top_k(s + b[l])                b[l] the selection bias: selects only
      w_e = routed_scaling_factor s_e / sum_S s        (norm_topk_prob)
      h   = h + sum_{e in S, e held} w_e Wd_e(silu(Wg_e y) * (Wu_e y))
              + Wd_sh(silu(Wg_sh y) * (Wu_sh y))       shared, n_shared x width
      after the step: b[l]_e += bias_update_speed sign(mean_i load_i - load_e)
                      load_e: assignments to e of the step's tokens
      balance, a row of T tokens: f_e = experts / (top_k T) sum_t [e in S_t]
                                  P_e = (1/T) sum_t s_(e,t) / sum_j s_(j,t)
      loss += aux_loss_alpha * mean over rows of sum_e f_e P_e
                                  (f a count: the gradient flows through P)

input ``h = E[ids]``; output ``logits = RMSNorm(h; g_f) W_head^T`` with a
head of its own (untied). The REPORTED loss is the softmax cross-entropy of
the next token, the mean over every position; the gradient is that of the
cross-entropy plus the balance terms.

Held experts: the configuration's ``n_routed_experts`` experts from
``first_held_expert``, of the router's ``router_num_experts``; the layer's
routed part is what the held experts give, as on the one device of an
expert-parallel job that holds them. The shared experts are whole.

Departures from the source, each a line of the configuration's
``assumed``: rotate-half over the rotary dims (the checkpoint pairs
interleaved dims: the same up to a fixed permutation of those weight
columns), the latent norm's eps, the bias's speed and the balance factor
(DeepSeek-V3's report, arXiv:2412.19437), the loads of this chip's tokens
alone, the optimizer (Adam without decay), the initialization
(`init_params`: the source ships trained weights), sequences that start at
position 0 with no packing. Weights are stored [out, in] as
`FullyConnected` takes them; an expert stack is [held, out, in], gate rows
before up rows, as the dense MLP's and the shared experts' matrices.

Precision ``policy``: ``f32`` float32 at ``highest`` (the reference);
``bf16`` the configuration's own mixed precision written plainly (weight
and score products on bfloat16 operands with float32 accumulation, the
router float32 at ``highest``, everything else float32); ``fp8`` the
control, the nearest precision below: as ``bf16`` with every weight
product's operands rounded to float8_e4m3 and its cotangent to
float8_e5m2, and the router on bfloat16 operands.

Memory: the backward pass keeps one layer's input a layer, a block of
queries' scores a block, an expert's inputs an expert and a block of
tokens' MLP and head inputs a block (`jax.checkpoint`), which changes no
value.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.refs.granite_hybrid import (_einsum, _product, rms_norm,
                                           seed_key)
from benchmark.refs.smallthinker import make_pool, rotary  # noqa: F401

F32 = jnp.float32


# -- shapes and the seeded generators -----------------------------------------

def layer_kinds(config):
    """["dense" or "experts"] of the layers held."""
    return ["experts" if i >= config["first_k_dense_replace"] else "dense"
            for i in range(config["num_hidden_layers"])]


def param_shapes(config):
    """{parameter: shape}, written from the source's module list."""
    c = config
    hidden, heads = c["hidden_size"], c["num_attention_heads"]
    nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    out = {"embed_weight": (c["vocab_size"], hidden)}
    for i, kind in enumerate(layer_kinds(c)):
        p = "l%d_" % i
        out[p + "input_norm_weight"] = (hidden,)
        out[p + "q_weight"] = (heads * (nope + rope), hidden)
        out[p + "kv_a_weight"] = (c["kv_lora_rank"] + rope, hidden)
        out[p + "kv_norm_weight"] = (c["kv_lora_rank"],)
        out[p + "kv_b_weight"] = (heads * (nope + c["v_head_dim"]),
                                  c["kv_lora_rank"])
        out[p + "o_weight"] = (hidden, heads * c["v_head_dim"])
        out[p + "post_norm_weight"] = (hidden,)
        if kind == "dense":
            out[p + "mlp_gate_up_weight"] = (2 * c["intermediate_size"],
                                             hidden)
            out[p + "mlp_down_weight"] = (hidden, c["intermediate_size"])
            continue
        width = c["moe_intermediate_size"]
        shared = c["n_shared_experts"] * width
        out[p + "router_weight"] = (c["router_num_experts"], hidden)
        out[p + "experts_gate_up_weight"] = (c["n_routed_experts"],
                                             2 * width, hidden)
        out[p + "experts_down_weight"] = (c["n_routed_experts"], hidden,
                                          width)
        out[p + "shared_gate_up_weight"] = (2 * shared, hidden)
        out[p + "shared_down_weight"] = (hidden, shared)
    out["final_norm_weight"] = (hidden,)
    out["head_weight"] = (c["vocab_size"], hidden)
    return out


def bias_names(config):
    """The selection biases, one an expert layer."""
    return ["l%d_router_bias" % i for i, kind in enumerate(layer_kinds(config))
            if kind == "experts"]


def init_biases(config):
    """Every selection bias at its start, zeros [router_num_experts]."""
    return {n: jnp.zeros((config["router_num_experts"],), F32)
            for n in bias_names(config)}


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


# -- the layers ---------------------------------------------------------------

def attention(params, p, x, config, policy, block=256):
    """Latent attention: masked softmax, a block of queries at a time."""
    c = config
    heads, latent = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    bsz, t, _ = x.shape
    q = _product(policy, x, params[p + "q_weight"]).reshape(bsz, t, heads, -1)
    kva = _product(policy, x, params[p + "kv_a_weight"])
    ckv = rms_norm(kva[..., :latent], params[p + "kv_norm_weight"],
                   c["kv_norm_eps"])
    kv = _product(policy, ckv, params[p + "kv_b_weight"]).reshape(
        bsz, t, heads, -1)
    k_pe = rotary(kva[..., latent:].reshape(bsz, t, 1, rope), c["rope_theta"])
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:],
                                               c["rope_theta"])], axis=-1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (bsz, t, heads, rope))],
                        axis=-1)
    v = kv[..., nope:]
    block = block if t % block == 0 else t

    @jax.checkpoint
    def rows(at):
        qb, start = at                                  # [B, block, H, D]
        s = _einsum(policy, "bqhd,bkhd->bhqk", qb, k) * (nope + rope) ** -0.5
        i = (start + jnp.arange(block))[:, None]
        seen = jnp.arange(t)[None] <= i
        prob = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return _einsum(policy, "bhqk,bkhd->bqhd", prob, v)

    o = lax.map(rows, (jnp.moveaxis(q.reshape(bsz, t // block, block,
                                              heads, -1), 1, 0),
                       jnp.arange(t // block) * block))
    o = jnp.moveaxis(o, 0, 1).reshape(bsz, t, -1)
    return _product(policy, o, params[p + "o_weight"])


def swiglu(policy, y, gate_up, down, block=2048):
    """Wd(silu(Wg y) * (Wu y)), gate rows before up rows; ``block`` tokens
    at a time."""
    width = down.shape[-1]

    @jax.checkpoint
    def rows(yb):
        both = _product(policy, yb, gate_up)
        return _product(policy, jax.nn.silu(both[..., :width])
                        * both[..., width:], down)

    flat = y.reshape(-1, y.shape[-1])
    block = block if flat.shape[0] % block == 0 else flat.shape[0]
    out = lax.map(rows, flat.reshape(-1, block, flat.shape[-1]))
    return out.reshape(y.shape[:-1] + (down.shape[0],))


def routing(params, bias, p, y, config, policy):
    """(each held expert's weight on each token [B, T, held]: w_e where
    the router chose e, 0 where it did not; the layer's balance loss; the
    load of each of the router's experts [experts])."""
    c = config
    w = params[p + "router_weight"]
    if policy == "fp8":
        logits = jnp.matmul(y.astype(jnp.bfloat16), w.T.astype(jnp.bfloat16),
                            preferred_element_type=F32)
    else:
        logits = jnp.matmul(y, w.T, precision=lax.Precision.HIGHEST)
    experts, top_k = c["router_num_experts"], c["num_experts_per_tok"]
    s = jax.nn.sigmoid(logits)                             # [B, T, experts]
    chosen = lax.top_k(s + bias, top_k)[1]
    top = jnp.take_along_axis(s, chosen, axis=-1)
    if c["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    top = c["routed_scaling_factor"] * top
    picked = chosen[..., :, None] == jnp.arange(experts)  # [B, T, k, experts]
    counts = jnp.sum(picked, axis=(1, 2)).astype(F32)    # [B, experts]
    t = y.shape[1]
    f = experts / (top_k * t) * counts
    share = jnp.mean(s / jnp.sum(s, axis=-1, keepdims=True), axis=1)
    balance = jnp.mean(jnp.sum(f * share, axis=-1))
    held = c["first_held_expert"] + jnp.arange(c["n_routed_experts"])
    mine = chosen[..., :, None] == held                  # [B, T, k, held]
    weight = jnp.sum(jnp.where(mine, top[..., None], 0.0), axis=-2)
    return weight, balance, jnp.sum(counts, axis=0)


def experts(params, p, y, weight, policy):
    """sum_e weight_e * Wd_e(silu(Wg_e y) * (Wu_e y)) over the held
    experts, every expert on every token, one expert at a time."""
    @jax.checkpoint
    def one(gate_up, down, w):
        return w[..., None] * swiglu(policy, y, gate_up, down)

    def add(total, at):
        return total + one(*at), None

    total, _ = lax.scan(add, jnp.zeros(y.shape, F32), (
        params[p + "experts_gate_up_weight"],
        params[p + "experts_down_weight"], jnp.moveaxis(weight, -1, 0)))
    return total


def forward(params, biases, ids, config, policy="f32"):
    """(the head's input, RMSNorm(h; g_f) [rows, T, hidden]; the balance
    losses summed over the layers; {bias name: the layer's loads
    [experts]}), float32."""
    c = config
    eps = c["rms_norm_eps"]
    table = params["embed_weight"]
    if policy != "f32":
        table = table.astype(jnp.bfloat16)
    h = table[ids].astype(F32)
    balance, loads = 0.0, {}
    for i, kind in enumerate(layer_kinds(c)):
        p = "l%d_" % i

        @jax.checkpoint
        def layer(h, params, bias, p=p, kind=kind):
            x = rms_norm(h, params[p + "input_norm_weight"], eps)
            h = h + attention(params, p, x, c, policy)
            y = rms_norm(h, params[p + "post_norm_weight"], eps)
            if kind == "dense":
                return h + swiglu(policy, y, params[p + "mlp_gate_up_weight"],
                                  params[p + "mlp_down_weight"]), 0.0, None
            weight, balance, load = routing(params, bias, p, y, c, policy)
            h = h + experts(params, p, y, weight, policy) \
                + swiglu(policy, y, params[p + "shared_gate_up_weight"],
                         params[p + "shared_down_weight"])
            return h, balance, load

        bias = biases.get(p + "router_bias")
        h, term, load = layer(
            h, {n: w for n, w in params.items() if n.startswith(p)}, bias)
        balance = balance + term
        if load is not None:
            loads[p + "router_bias"] = load
    return rms_norm(h, params["final_norm_weight"], eps), balance, loads


def loss_fn(params, biases, ids, labels, config, policy="f32"):
    """(cross-entropy + aux_loss_alpha times the balance losses, the
    gradient's loss; (the mean cross-entropy over every position, the
    reported loss; softmax outputs [rows x T, vocabulary]; the loads)),
    float32."""
    x, balance, loads = forward(params, biases, ids, config, policy)
    x = x.reshape(-1, x.shape[-1])
    labels = labels.reshape(-1).astype(jnp.int32)
    block = 2048 if x.shape[0] % 2048 == 0 else x.shape[0]

    @jax.checkpoint
    def head(at):                   # the logits of ``block`` positions
        xb, lb = at
        logp = jax.nn.log_softmax(_product(policy, xb, params["head_weight"]),
                                  axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, lb[:, None], axis=1)), \
            jnp.exp(logp)

    nll, probs = lax.map(head, (x.reshape(-1, block, x.shape[-1]),
                                labels.reshape(-1, block)))
    ce = jnp.sum(nll) / x.shape[0]
    return ce + np.float32(config["aux_loss_alpha"]) * balance, \
        (ce, probs.reshape(x.shape[0], -1), loads)


def moved_biases(biases, loads, config):
    """Each bias after a step: + speed x sign(mean load - the load)."""
    speed = np.float32(config["bias_update_speed"])
    return {n: b + speed * jnp.sign(jnp.mean(loads[n]) - loads[n])
            for n, b in biases.items()}


# -- Adam, as the configuration states it -------------------------------------

def make_step(config, optimizer, policy="f32"):
    """One jitted step: (params, biases, mean, var, t, ids, labels) ->
    (params, biases, mean, var, loss, softmax outputs). Adam with bias
    correction folded into the rate, no decay:

        m = b1 m + (1 - b1) g;  v = b2 v + (1 - b2) g^2
        w = w - lr sqrt(1 - b2^t) / (1 - b1^t) * m / (sqrt(v) + eps)

    and the selection biases moved by the step's loads."""
    lr, b1, b2, eps = (np.float32(optimizer[k]) for k in
                       ("learning_rate", "beta1", "beta2", "epsilon"))
    grad = jax.value_and_grad(
        functools.partial(loss_fn, config=config, policy=policy),
        has_aux=True)

    # mxanalyze: allow(donation-hazard): the reference imports nothing of the program, its donation policy included; 11 GB of float32 state are written over in place on the chip, and a CPU run ignores the request
    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def step(params, biases, mean, var, t, ids, labels):
        (_, (loss, probs, loads)), grads = grad(params, biases, ids, labels)
        rate = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        new_p, new_m, new_v = {}, {}, {}
        # mxanalyze: allow(dispatch-amplification): Adam written out leaf by leaf, as a plain reference does; the leaves have different shapes
        for name, w in params.items():
            g = grads[name]
            new_m[name] = b1 * mean[name] + (1 - b1) * g
            new_v[name] = b2 * var[name] + (1 - b2) * g * g
            new_p[name] = w - rate * new_m[name] / (jnp.sqrt(new_v[name])
                                                    + eps)
        return new_p, moved_biases(biases, loads, config), new_m, new_v, \
            loss, probs

    return step


def follow(config, optimizer, params, batches, policy="f32", biases=None):
    """Drive the reference through ``batches`` [(ids, labels), ...] from
    ``params`` (which it consumes) and the selection biases (zeros by
    default). Returns each step's loss, the first step's softmax outputs,
    Adam's first moment after step 1 (``first_mean`` = (1 - beta1) g,
    from which the first gradient follows), both moments and the
    parameters at the end, and the selection biases after every step."""
    step = make_step(config, optimizer, policy)
    biases = init_biases(config) if biases is None else biases
    mean = {n: jnp.zeros_like(w) for n, w in params.items()}
    var = {n: jnp.zeros_like(w) for n, w in params.items()}
    losses, first_mean, first_probs, after = [], None, None, []
    for i, (ids, labels) in enumerate(batches):
        params, biases, mean, var, loss, probs = step(
            params, biases, mean, var, np.float32(i + 1), ids, labels)
        losses.append(loss)
        after.append({n: np.asarray(b) for n, b in biases.items()})
        if i == 0:
            first_mean = {n: np.asarray(v) for n, v in mean.items()}
            first_probs = np.asarray(probs)
        del probs
    return {"losses": [float(v) for v in losses], "first_mean": first_mean,
            "first_probs": first_probs, "params": params, "mean": mean,
            "var": var, "biases": after}
