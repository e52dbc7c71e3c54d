"""Operations and least bytes of a training step of a sparse-expert token
model with sliding-window and full attention (`refs/smallthinker.py`), from
the configuration's SHAPES, never from XLA's `cost_analysis`. 2 operations
to a multiply-add; a training step runs three products to every forward
product (forward, the gradient to each operand). Recomputed work is never
credited: the step recomputes every layer's forward pass and none of it is
counted.

Counted: 6 x (matrix parameters) a token for attention's projections, the
router and the head (the embedding's lookup is no product); the held
experts at the EXPECTED number of assignments, tokens x top_k x held /
router width (a seeded router at initialization spreads the tokens near
evenly, so that is what a step routes to the held experts, within a few
hundredths); attention's two products over the (query, key) pairs each
layer's mask admits: T (T + 1) / 2 causal, T W - W (W - 1) / 2 under a
window of W keys."""

from benchmark.refs.smallthinker import layer_kinds


def dense_parameters(config):
    """Weights that enter a product for every token: q, k, v, o and the
    router of every layer, and the head."""
    c = config
    hidden, head = c["hidden_size"], c["head_dim"]
    attention = 2 * c["num_attention_heads"] * head * hidden \
        + 2 * c["num_key_value_heads"] * head * hidden
    router = c["moe_router_num_experts"] * hidden
    return len(layer_kinds(c)) * (attention + router) \
        + c["vocab_size"] * hidden


def expected_assignments(config, rows, tokens):
    """(token, expert) pairs a layer routes to the held experts, expected."""
    c = config
    return rows * tokens * c["moe_num_active_primary_experts"] \
        * c["moe_num_primary_experts"] / c["moe_router_num_experts"]


def experts_train_flops(config, rows, tokens):
    """The held experts' grouped products, all layers, one step."""
    c = config
    per_assignment = 3 * c["moe_ffn_hidden_size"] * c["hidden_size"]
    return 6 * per_assignment * expected_assignments(config, rows, tokens) \
        * len(layer_kinds(c))


def experts_train_bytes(config, rows, tokens, itemsize=2):
    """The least the grouped products of one step move: each of the six
    products (gate-up and down forward; the gradient to each operand of
    each) reads its two operands and writes its result once, ``itemsize``
    bytes an element. Nothing between them is counted twice."""
    c = config
    rows_ = expected_assignments(config, rows, tokens)
    d, f = c["hidden_size"], c["moe_ffn_hidden_size"]
    w_up = c["moe_num_primary_experts"] * 2 * f * d
    w_down = c["moe_num_primary_experts"] * d * f
    a_in, a_both, a_mid = rows_ * d, rows_ * 2 * f, rows_ * f
    products = [
        (a_in, w_up, a_both),        # forward gate-up
        (a_mid, w_down, a_in),       # forward down
        (a_in, w_down, a_mid),       # gradient to the down product's input
        (a_mid, a_in, w_down),       # gradient to the down weights
        (a_both, w_up, a_in),        # gradient to the gate-up input
        (a_in, a_both, w_up),        # gradient to the gate-up weights
    ]
    return itemsize * len(layer_kinds(c)) * sum(sum(p) for p in products)


def attention_pairs(config, tokens):
    """(query, key) pairs a row's layers admit, summed over the layers."""
    c, t = config, tokens
    full = t * (t + 1) // 2
    w = min(c["sliding_window_size"], t)
    windowed = t * w - w * (w - 1) // 2
    return sum(windowed if win else full for win, _ in layer_kinds(c))


def attention_train_flops(config, rows, tokens):
    """Scores and read-out over the admitted pairs, forward and both
    gradients."""
    c = config
    width = c["num_attention_heads"] * c["head_dim"]
    return 3 * 2 * 2 * width * rows * attention_pairs(config, tokens)


def attention_train_bytes(config, rows, tokens, itemsize=2):
    """The least attention's kernels move: the forward reads q, k, v and
    writes o; the backward reads q, k, v, o and o's cotangent and writes
    the three gradients."""
    c = config
    q = rows * tokens * c["num_attention_heads"] * c["head_dim"]
    kv = rows * tokens * c["num_key_value_heads"] * c["head_dim"]
    forward = 2 * q + 2 * kv
    backward = 3 * q + 2 * kv + q + 2 * kv
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
