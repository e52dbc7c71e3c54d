"""SmallThinker (`smallthinker`): every layer grouped-query attention, with
a rotary term and a sliding window in three of every four layers and
neither in the fourth, then a layer of routed ReGLU experts whose router
reads the layer's input BEFORE attention; untied embedding and head. As
ONE loss symbol of registered operators.

    sym = smallthinker.symbol(config, dtype="bfloat16", recompute=True)
    mod = mx.mod.Module(sym)          # inputs data [rows, T], softmax_label

``config`` holds the keys of the published ``config.json``
(https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct);
``sliding_window_layout`` and ``rope_layout`` are cut to
``num_hidden_layers``. ``moe_num_primary_experts`` counts the experts this
device holds, ``moe_router_num_experts`` the router's width (all the
experts of a layer) and ``moe_first_held_expert`` the first one held: the
`moe` op computes the held experts' part of each layer (expert
parallelism's share, `ops/moe_ops.py`). The equations are written out in
`benchmark/refs/smallthinker.py`, the plain float32 implementation the
tests hold this symbol to.

Mixed precision and recomputation as in `granite_hybrid`: float32
variables with a `Cast` where a matrix enters a product, one
`mirror_stage` segment a layer, `profiler_scope` names ``attention``,
``moe`` and ``lm_head``. The router reads the float32 norm of the layer's
input; attention reads it in ``dtype``.
"""
import contextlib

from .. import symbol as sym
from ..attribute import AttrScope


def layers(config):
    """[(windowed, rotary)] of every layer held."""
    c, n = config, config["num_hidden_layers"]
    return [(bool(w), bool(r)) for w, r in
            zip(c["sliding_window_layout"][:n], c["rope_layout"][:n])]


def param_shapes(config):
    """{parameter name: shape} in the order the symbol lists them."""
    c = config
    hidden, head = c["hidden_size"], c["head_dim"]
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    held, width = c["moe_num_primary_experts"], c["moe_ffn_hidden_size"]
    shapes = {"embed_weight": (c["vocab_size"], hidden)}
    for i in range(len(layers(c))):
        p = "l%d_" % i
        shapes[p + "input_norm_weight"] = (hidden,)
        shapes[p + "q_weight"] = (heads * head, hidden)
        shapes[p + "k_weight"] = (kv * head, hidden)
        shapes[p + "v_weight"] = (kv * head, hidden)
        shapes[p + "o_weight"] = (hidden, heads * head)
        shapes[p + "post_norm_weight"] = (hidden,)
        shapes[p + "router_weight"] = (c["moe_router_num_experts"], hidden)
        shapes[p + "experts_gate_up_weight"] = (held, 2 * width, hidden)
        shapes[p + "experts_down_weight"] = (held, hidden, width)
    shapes["final_norm_weight"] = (hidden,)
    shapes["head_weight"] = (c["vocab_size"], hidden)
    return shapes


def symbol(config, dtype="float32", recompute=False):
    """The loss symbol: `SoftmaxOutput` over [rows x T, vocabulary], its
    gradient the mean over all positions' cross-entropy. ``data`` holds
    token ids [rows, T], ``softmax_label`` the next id of every position."""
    c = config
    shapes = param_shapes(c)
    weights = {n: sym.Variable(n, shape=s, dtype="float32")
               for n, s in shapes.items()}
    eps = c["rms_norm_eps"]
    mixed = dtype != "float32"

    def operand(name):
        w = weights[name]
        return sym.Cast(w, dtype=dtype) if mixed else w

    def dense(x, name):
        return sym.FullyConnected(x, operand(name), no_bias=True,
                                  flatten=False, num_hidden=shapes[name][0])

    def residual(h, branch):
        return h + (sym.Cast(branch, dtype="float32") if mixed else branch)

    def attention(x, p, windowed, rotary):
        heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
        q = sym.Reshape(dense(x, p + "q_weight"), shape=(0, 0, heads, -1))
        k = sym.Reshape(dense(x, p + "k_weight"), shape=(0, 0, kv, -1))
        v = sym.Reshape(dense(x, p + "v_weight"), shape=(0, 0, kv, -1))
        if rotary:
            q = sym.rope(q, theta=c["rope_theta"])
            k = sym.rope(k, theta=c["rope_theta"])
        bound = {"window": c["sliding_window_size"]} if windowed else {}
        o = sym.contrib.flash_attention(
            q, k, v, causal=True, scale=c["head_dim"] ** -0.5, **bound)
        return dense(sym.Reshape(o, shape=(0, 0, -1)), p + "o_weight")

    def experts(x, y, p):
        return sym.moe(
            x, y, weights[p + "router_weight"],
            operand(p + "experts_gate_up_weight"),
            operand(p + "experts_down_weight"),
            first_held=c["moe_first_held_expert"],
            top_k=c["moe_num_active_primary_experts"])

    ids = sym.Variable("data")
    label = sym.Variable("softmax_label")
    h = sym.Embedding(ids, operand("embed_weight"),
                      input_dim=c["vocab_size"], output_dim=c["hidden_size"])
    if mixed:
        h = sym.Cast(h, dtype="float32")
    for i, (windowed, rotary) in enumerate(layers(c)):
        p = "l%d_" % i
        stage = AttrScope(mirror_stage="layer%d" % i) if recompute \
            else contextlib.nullcontext()
        with stage:
            x = sym.rms_norm(h, weights[p + "input_norm_weight"], eps=eps)
            with AttrScope(profiler_scope="attention"):
                a = attention(sym.Cast(x, dtype=dtype) if mixed else x, p,
                              windowed, rotary)
            h = residual(h, a)
            y = sym.rms_norm(h, weights[p + "post_norm_weight"], eps=eps,
                             dtype=dtype)
            with AttrScope(profiler_scope="moe"):
                h = residual(h, experts(x, y, p))
    with AttrScope(profiler_scope="lm_head"):
        x = sym.rms_norm(h, weights["final_norm_weight"], eps=eps,
                         dtype=dtype)
        logits = dense(x, "head_weight")
        if mixed:
            logits = sym.Cast(logits, dtype="float32")
        logits = sym.Reshape(logits, shape=(-1, c["vocab_size"]))
        return sym.SoftmaxOutput(logits, sym.Reshape(label, shape=(-1,)),
                                 normalization="valid", name="softmax")
