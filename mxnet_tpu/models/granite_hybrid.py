"""Granite-4.0-H (`granitemoehybrid`): Mamba-2 layers beside grouped-query
attention layers, a shared SwiGLU MLP in every layer, a tied embedding and
four scalar multipliers, as ONE loss symbol of registered operators.

    sym = granite_hybrid.symbol(config, dtype="bfloat16", recompute=True)
    mod = mx.mod.Module(sym)          # inputs data [rows, T], softmax_label

``config`` holds the keys of the published ``config.json``
(https://huggingface.co/ibm-granite/granite-4.0-h-micro); ``layer_types``
is cut to ``num_hidden_layers``. The equations are written out in
`benchmark/refs/granite_hybrid.py`, the plain float32 implementation the
tests hold this symbol to.

Mixed precision (``dtype="bfloat16"``): every parameter is a float32
variable, the master weight the optimizer updates, and a `Cast` node brings
a matrix to ``dtype`` where it enters a product; the casts live inside
their layer, so a layer's low-precision copies last as long as the layer
does. The residual stream, the norms' arithmetic, the decays, the state and
the softmax are float32.

Recomputation (``recompute=True``): every layer is built under
`mx.AttrScope(mirror_stage=...)`, which the executor evaluates as one
checkpointed segment (`executor._plan_mirror_segments`). The mixers, the
MLP and the head carry `profiler_scope` names (``mamba_mixer``,
``attention``, ``mlp``, ``lm_head``) for the device trace.
"""
import contextlib

from .. import symbol as sym
from ..attribute import AttrScope


def param_shapes(config):
    """{parameter name: shape} in the order the symbol lists them."""
    c = config
    hidden, mlp = c["hidden_size"], c["shared_intermediate_size"]
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    head = hidden // heads
    inner = c["mamba_expand"] * hidden
    m_heads = c["mamba_n_heads"]
    conv = inner + 2 * c["mamba_n_groups"] * c["mamba_d_state"]
    shapes = {"embed_weight": (c["vocab_size"], hidden)}
    for i, kind in enumerate(c["layer_types"][:c["num_hidden_layers"]]):
        p = "l%d_" % i
        shapes[p + "norm1_weight"] = (hidden,)
        if kind == "mamba":
            shapes[p + "in_proj_weight"] = (inner + conv + m_heads, hidden)
            shapes[p + "conv_weight"] = (conv, c["mamba_d_conv"])
            shapes[p + "conv_bias"] = (conv,)
            shapes[p + "dt_bias"] = (m_heads,)
            shapes[p + "A_log"] = (m_heads,)
            shapes[p + "D"] = (m_heads,)
            shapes[p + "mixer_norm_weight"] = (inner,)
            shapes[p + "out_proj_weight"] = (hidden, inner)
        elif kind == "attention":
            shapes[p + "q_weight"] = (heads * head, hidden)
            shapes[p + "k_weight"] = (kv * head, hidden)
            shapes[p + "v_weight"] = (kv * head, hidden)
            shapes[p + "o_weight"] = (hidden, heads * head)
        else:
            raise ValueError("layer type %r" % kind)
        shapes[p + "norm2_weight"] = (hidden,)
        shapes[p + "mlp_in_weight"] = (2 * mlp, hidden)
        shapes[p + "mlp_out_weight"] = (hidden, mlp)
    shapes["final_norm_weight"] = (hidden,)
    return shapes


def symbol(config, dtype="float32", recompute=False):
    """The loss symbol: `SoftmaxOutput` over [rows x T, vocabulary], its
    gradient the mean over all positions' cross-entropy. ``data`` holds
    token ids [rows, T], ``softmax_label`` the next id of every position."""
    c = config
    shapes = param_shapes(c)
    weights = {n: sym.Variable(n, shape=s, dtype="float32")
               for n, s in shapes.items()}
    hidden, eps = c["hidden_size"], c["rms_norm_eps"]
    mixed = dtype != "float32"

    def operand(name):
        w = weights[name]
        return sym.Cast(w, dtype=dtype) if mixed else w

    def dense(x, name):
        return sym.FullyConnected(x, operand(name), no_bias=True,
                                  flatten=False, num_hidden=shapes[name][0])

    def part(x, begin, end):
        return sym.slice_axis(x, axis=2, begin=begin, end=end)

    def residual(h, branch):
        if mixed:
            branch = sym.Cast(branch, dtype="float32")
        return h + branch * c["residual_multiplier"]

    def mamba(x, p):
        groups, state = c["mamba_n_groups"], c["mamba_d_state"]
        heads, head = c["mamba_n_heads"], c["mamba_d_head"]
        inner = heads * head
        conv = inner + 2 * groups * state
        proj = dense(x, p + "in_proj_weight")
        z = part(proj, 0, inner)
        dt = part(proj, inner + conv, None)
        xbc = sym.silu(sym.causal_conv1d(
            part(proj, inner, inner + conv), weights[p + "conv_weight"],
            weights[p + "conv_bias"]))
        if mixed:
            dt = sym.Cast(dt, dtype="float32")
        dt = sym.Activation(sym.broadcast_add(dt, weights[p + "dt_bias"]),
                            act_type="softrelu")
        y = sym.mamba2_ssd(
            sym.Reshape(part(xbc, 0, inner), shape=(0, 0, heads, head)),
            dt, sym.negative(sym.exp(weights[p + "A_log"])),
            sym.Reshape(part(xbc, inner, inner + groups * state),
                        shape=(0, 0, groups, state)),
            sym.Reshape(part(xbc, inner + groups * state, None),
                        shape=(0, 0, groups, state)),
            weights[p + "D"], chunk_size=c["mamba_chunk_size"])
        y = sym.rms_norm(sym.Reshape(y, shape=(0, 0, -1)),
                         weights[p + "mixer_norm_weight"], z, eps=eps)
        return dense(y, p + "out_proj_weight")

    def attention(x, p):
        heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
        q = sym.Reshape(dense(x, p + "q_weight"), shape=(0, 0, heads, -1))
        k = sym.Reshape(dense(x, p + "k_weight"), shape=(0, 0, kv, -1))
        v = sym.Reshape(dense(x, p + "v_weight"), shape=(0, 0, kv, -1))
        # no rotary or other position term ("nope"); the scale is the
        # model's own multiplier, not 1/sqrt(head)
        o = sym.contrib.flash_attention(
            q, k, v, causal=True, scale=c["attention_multiplier"])
        return dense(sym.Reshape(o, shape=(0, 0, -1)), p + "o_weight")

    def mlp(x, p):
        width = c["shared_intermediate_size"]
        both = dense(x, p + "mlp_in_weight")
        return dense(sym.silu(part(both, 0, width)) * part(both, width, None),
                     p + "mlp_out_weight")

    def norm(h, name):
        return sym.rms_norm(h, weights[name], eps=eps, dtype=dtype)

    ids = sym.Variable("data")
    label = sym.Variable("softmax_label")
    embed = operand("embed_weight")     # one variable, used twice: tied
    h = sym.Embedding(ids, embed, input_dim=c["vocab_size"],
                      output_dim=hidden)
    if mixed:
        h = sym.Cast(h, dtype="float32")
    h = h * c["embedding_multiplier"]
    for i, kind in enumerate(c["layer_types"][:c["num_hidden_layers"]]):
        p = "l%d_" % i
        stage = AttrScope(mirror_stage="layer%d" % i) if recompute \
            else contextlib.nullcontext()
        with stage:
            x = norm(h, p + "norm1_weight")
            mixer, scope = (mamba, "mamba_mixer") if kind == "mamba" \
                else (attention, "attention")
            with AttrScope(profiler_scope=scope):
                x = mixer(x, p)
            h = residual(h, x)
            x = norm(h, p + "norm2_weight")
            with AttrScope(profiler_scope="mlp"):
                x = mlp(x, p)
            h = residual(h, x)
    with AttrScope(profiler_scope="lm_head"):
        x = norm(h, "final_norm_weight")
        logits = sym.FullyConnected(x, embed, no_bias=True, flatten=False,
                                    num_hidden=c["vocab_size"])
        if mixed:
            logits = sym.Cast(logits, dtype="float32")
        logits = sym.Reshape(logits / c["logits_scaling"],
                             shape=(-1, c["vocab_size"]))
        return sym.SoftmaxOutput(logits, sym.Reshape(label, shape=(-1,)),
                                 normalization="valid", name="softmax")
