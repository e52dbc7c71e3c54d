"""Operations and least bytes of a training step of a latent-attention
sparse-expert token model with a leading dense layer and shared experts
(`refs/moonlight.py`), from the configuration's SHAPES, never from XLA's
`cost_analysis`. 2 operations to a multiply-add; a training step runs
three products to every forward product (forward, the gradient to each
operand). Recomputed work is never credited: the step recomputes every
layer's forward pass and none of it is counted.

Counted: 6 x (matrix parameters) a token for attention's projections (q,
the latent's down- and up-projection, o), the dense layer's MLP, the
router, the shared experts and the head (the embedding's lookup is no
product); the held experts at the EXPECTED number of assignments, tokens x
top_k x held / router width (a seeded router at initialization spreads the
tokens near evenly, and its selection bias starts at zero); attention's
two products over the causal (query, key) pairs, T (T + 1) / 2 a row and
layer: the scores over the query-key width (no-position plus rotary dims)
and the read-out over the value width, 2 (192 + 128) operations a pair and
head at the published widths, forward, times 3 for training."""

from benchmark.refs.moonlight import layer_kinds


def dense_parameters(config):
    """Weights that enter a product for every token: every layer's
    attention projections, the dense layer's MLP, the expert layers'
    routers and shared experts, and the head."""
    c = config
    hidden, heads = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    latent, dv = c["kv_lora_rank"], c["v_head_dim"]
    attention = heads * qk * hidden \
        + (latent + c["qk_rope_head_dim"]) * hidden \
        + heads * (c["qk_nope_head_dim"] + dv) * latent + hidden * heads * dv
    total = c["vocab_size"] * hidden
    for kind in layer_kinds(c):
        total += attention
        if kind == "dense":
            total += 3 * c["intermediate_size"] * hidden
        else:
            total += c["router_num_experts"] * hidden \
                + 3 * c["n_shared_experts"] * c["moe_intermediate_size"] \
                * hidden
    return total


def expert_layers(config):
    return sum(kind == "experts" for kind in layer_kinds(config))


def expected_assignments(config, rows, tokens):
    """(token, expert) pairs a layer routes to the held experts, expected."""
    c = config
    return rows * tokens * c["num_experts_per_tok"] \
        * c["n_routed_experts"] / c["router_num_experts"]


def experts_train_flops(config, rows, tokens):
    """The held experts' grouped products, all expert layers, one step."""
    c = config
    per_assignment = 3 * c["moe_intermediate_size"] * c["hidden_size"]
    return 6 * per_assignment * expected_assignments(config, rows, tokens) \
        * expert_layers(config)


def attention_pairs(config, tokens):
    """Causal (query, key) pairs a row's layers admit, summed over the
    layers."""
    return len(layer_kinds(config)) * tokens * (tokens + 1) // 2


def attention_train_flops(config, rows, tokens):
    """Scores (over the query-key width) and read-out (over the value
    width) over the causal pairs, forward and both gradients."""
    c = config
    width = c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]
    return 3 * 2 * width * c["num_attention_heads"] * rows \
        * attention_pairs(config, tokens)


def attention_train_bytes(config, rows, tokens, itemsize=2):
    """The least attention's kernels move: the forward reads q, k (the
    query-key width) and v and writes o (the value width); the backward
    reads q, k, v, o and o's cotangent and writes the three gradients."""
    c = config
    positions = rows * tokens * c["num_attention_heads"]
    qk = positions * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"])
    vo = positions * c["v_head_dim"]
    forward = 2 * qk + 2 * vo
    backward = 2 * qk + 3 * vo + 2 * qk + vo
    return itemsize * len(layer_kinds(c)) * (forward + backward)


def train_flops(config, rows, tokens):
    """One training step over ``rows`` x ``tokens`` positions."""
    return 6 * dense_parameters(config) * rows * tokens \
        + experts_train_flops(config, rows, tokens) \
        + attention_train_flops(config, rows, tokens)


def least_seconds(flops, nbytes, config, peaks):
    """The roofline: the larger of the operations over the peak of the
    configuration's operand type and the bytes over the memory's rate."""
    return max(flops / peaks["flops_per_s"][config["dtype"]],
               nbytes / peaks["hbm_bytes_per_s"])
