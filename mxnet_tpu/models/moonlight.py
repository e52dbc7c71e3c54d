"""Moonlight (`deepseek_v3`): every layer latent attention (MLA), the first
``first_k_dense_replace`` layers then a SwiGLU MLP and every later one a
layer of routed SwiGLU experts under a sigmoid router with a selection
bias, beside shared experts; untied embedding and head. As ONE loss symbol
of registered operators.

    sym = moonlight.symbol(config, dtype="bfloat16", recompute=True)
    mod = mx.mod.Module(sym)          # inputs data [rows, T], softmax_label

``config`` holds the keys of the published ``config.json``
(https://huggingface.co/moonshotai/Moonlight-16B-A3B) and the sizes it does
not settle (``kv_norm_eps``, ``bias_update_speed``, ``aux_loss_alpha``).
``n_routed_experts`` counts the experts this device holds,
``router_num_experts`` the router's width and ``first_held_expert`` the
first one held: the `moe` op computes the held experts' part of each layer
(expert parallelism's share, `ops/moe_ops.py`). The equations are written
out in `benchmark/refs/moonlight.py`, the plain float32 implementation the
tests hold this symbol to.

Latent attention: queries are not compressed (``q_lora_rank`` null); keys
and values come out of a ``kv_lora_rank`` latent, normed, and one rotary
key of ``qk_rope_head_dim`` shared by every head. A head's queries and keys
are ``qk_nope_head_dim + qk_rope_head_dim`` wide, its values
``v_head_dim``: `flash_attention` takes a value width of its own.

Mixed precision and recomputation as in `granite_hybrid`: float32
variables with a `Cast` where a matrix enters a product, one
`mirror_stage` segment a layer. `profiler_scope` names: ``attention`` (the
whole block) with ``mla_latent`` inside it (the latent's projections and
norm, the rotary terms and the keys' assembly), ``dense_mlp``, ``moe``
(the routed part; the op's own ``moe_router`` inside), ``shared_experts``
and ``lm_head``. The selection biases ``l<i>_router_bias`` are auxiliary
states, moved by each training step.
"""
import contextlib

from .. import symbol as sym
from ..attribute import AttrScope


def expert_layer(config, i):
    """Whether layer ``i`` routes to experts (after the leading dense
    ones)."""
    return i >= config["first_k_dense_replace"]


def param_shapes(config):
    """{parameter name: shape} in the order the symbol lists them; the
    selection biases, auxiliary states, are not among them."""
    c = config
    hidden, heads = c["hidden_size"], c["num_attention_heads"]
    nope, rope, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    latent = c["kv_lora_rank"]
    shapes = {"embed_weight": (c["vocab_size"], hidden)}
    for i in range(c["num_hidden_layers"]):
        p = "l%d_" % i
        shapes[p + "input_norm_weight"] = (hidden,)
        shapes[p + "q_weight"] = (heads * (nope + rope), hidden)
        shapes[p + "kv_a_weight"] = (latent + rope, hidden)
        shapes[p + "kv_norm_weight"] = (latent,)
        shapes[p + "kv_b_weight"] = (heads * (nope + dv), latent)
        shapes[p + "o_weight"] = (hidden, heads * dv)
        shapes[p + "post_norm_weight"] = (hidden,)
        if not expert_layer(c, i):
            width = c["intermediate_size"]
            shapes[p + "mlp_gate_up_weight"] = (2 * width, hidden)
            shapes[p + "mlp_down_weight"] = (hidden, width)
            continue
        width = c["moe_intermediate_size"]
        held = c["n_routed_experts"]
        shared = c["n_shared_experts"] * width
        shapes[p + "router_weight"] = (c["router_num_experts"], hidden)
        shapes[p + "experts_gate_up_weight"] = (held, 2 * width, hidden)
        shapes[p + "experts_down_weight"] = (held, hidden, width)
        shapes[p + "shared_gate_up_weight"] = (2 * shared, hidden)
        shapes[p + "shared_down_weight"] = (hidden, shared)
    shapes["final_norm_weight"] = (hidden,)
    shapes["head_weight"] = (c["vocab_size"], hidden)
    return shapes


def symbol(config, dtype="float32", recompute=False):
    """The loss symbol: `SoftmaxOutput` over [rows x T, vocabulary], its
    gradient the mean over all positions' cross-entropy (the experts'
    balance loss rides the router's gradient inside `moe`). ``data`` holds
    token ids [rows, T], ``softmax_label`` the next id of every position."""
    c = config
    shapes = param_shapes(c)
    weights = {n: sym.Variable(n, shape=s, dtype="float32")
               for n, s in shapes.items()}
    eps = c["rms_norm_eps"]
    heads = c["num_attention_heads"]
    nope, rope, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    latent = c["kv_lora_rank"]
    mixed = dtype != "float32"

    def operand(name):
        w = weights[name]
        return sym.Cast(w, dtype=dtype) if mixed else w

    def dense(x, name):
        return sym.FullyConnected(x, operand(name), no_bias=True,
                                  flatten=False, num_hidden=shapes[name][0])

    def part(x, axis, begin, end):
        return sym.slice_axis(x, axis=axis, begin=begin, end=end)

    def residual(h, branch):
        return h + (sym.Cast(branch, dtype="float32") if mixed else branch)

    def attention(x, p):
        q = sym.Reshape(dense(x, p + "q_weight"), shape=(0, 0, heads, -1))
        with AttrScope(profiler_scope="attention/mla_latent"):
            kva = dense(x, p + "kv_a_weight")
            ckv = sym.rms_norm(part(kva, 2, 0, latent),
                               weights[p + "kv_norm_weight"],
                               eps=c["kv_norm_eps"], dtype=dtype)
            kv = sym.Reshape(dense(ckv, p + "kv_b_weight"),
                             shape=(0, 0, heads, -1))
            k_pe = sym.rope(sym.Reshape(part(kva, 2, latent, None),
                                        shape=(0, 0, 1, rope)),
                            theta=c["rope_theta"])
            k = sym.Concat(part(kv, 3, 0, nope),
                           sym.broadcast_to(k_pe, shape=(0, 0, heads, 0)),
                           dim=3)
            q = sym.Concat(part(q, 3, 0, nope),
                           sym.rope(part(q, 3, nope, None),
                                    theta=c["rope_theta"]), dim=3)
        o = sym.contrib.flash_attention(
            q, k, part(kv, 3, nope, nope + dv), causal=True,
            scale=(nope + rope) ** -0.5)
        return dense(sym.Reshape(o, shape=(0, 0, -1)), p + "o_weight")

    def swiglu(y, gate_up, down):
        width = shapes[down][1]
        both = dense(y, gate_up)
        return dense(sym.silu(part(both, 2, 0, width))
                     * part(both, 2, width, None), down)

    def experts(y32, y, p):
        bias = sym.Variable(p + "router_bias",
                            shape=(c["router_num_experts"],),
                            dtype="float32")
        return sym.moe(
            y32, y, weights[p + "router_weight"],
            operand(p + "experts_gate_up_weight"),
            operand(p + "experts_down_weight"), bias,
            first_held=c["first_held_expert"],
            top_k=c["num_experts_per_tok"], score=c["scoring_func"],
            routed_scale=c["routed_scaling_factor"], act="silu",
            bias_rate=c["bias_update_speed"],
            balance_alpha=c["aux_loss_alpha"])

    ids = sym.Variable("data")
    label = sym.Variable("softmax_label")
    h = sym.Embedding(ids, operand("embed_weight"),
                      input_dim=c["vocab_size"], output_dim=c["hidden_size"])
    if mixed:
        h = sym.Cast(h, dtype="float32")
    for i in range(c["num_hidden_layers"]):
        p = "l%d_" % i
        stage = AttrScope(mirror_stage="layer%d" % i) if recompute \
            else contextlib.nullcontext()
        with stage:
            x = sym.rms_norm(h, weights[p + "input_norm_weight"], eps=eps,
                             dtype=dtype)
            with AttrScope(profiler_scope="attention"):
                h = residual(h, attention(x, p))
            y32 = sym.rms_norm(h, weights[p + "post_norm_weight"], eps=eps)
            y = sym.Cast(y32, dtype=dtype) if mixed else y32
            if not expert_layer(c, i):
                with AttrScope(profiler_scope="dense_mlp"):
                    h = residual(h, swiglu(y, p + "mlp_gate_up_weight",
                                           p + "mlp_down_weight"))
                continue
            with AttrScope(profiler_scope="moe"):
                h = residual(h, experts(y32, y, p))
            with AttrScope(profiler_scope="shared_experts"):
                h = residual(h, swiglu(y, p + "shared_gate_up_weight",
                                       p + "shared_down_weight"))
    with AttrScope(profiler_scope="lm_head"):
        x = sym.rms_norm(h, weights["final_norm_weight"], eps=eps,
                         dtype=dtype)
        logits = dense(x, "head_weight")
        if mixed:
            logits = sym.Cast(logits, dtype="float32")
        logits = sym.Reshape(logits, shape=(-1, c["vocab_size"]))
        return sym.SoftmaxOutput(logits, sym.Reshape(label, shape=(-1,)),
                                 normalization="valid", name="softmax")
