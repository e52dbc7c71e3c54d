"""Operations and least bytes of a token model's training step, from the
configuration's SHAPES, never from XLA's `cost_analysis`. 2 operations to a
multiply-add; a training step runs three products to every forward product
(forward, the gradient to each operand). Recomputed work is never credited:
the step recomputes every layer's forward pass and none of it is counted.

Counted: 6 x (matrix parameters) a token for every weight product, the head
among them once (the embedding's lookup is no product); causal attention's
two products at half (the masked half is never needed); the state-space
scan's four products (`ssd_forward_flops`)."""


def layer_kinds(config):
    return list(config["layer_types"][:config["num_hidden_layers"]])


def matrix_parameters(config):
    """Weights that enter a product, the tied embedding once (as the head)."""
    c = config
    hidden, wide = c["hidden_size"], c["shared_intermediate_size"]
    head = hidden // c["num_attention_heads"]
    inner = c["mamba_n_heads"] * c["mamba_d_head"]
    gn = c["mamba_n_groups"] * c["mamba_d_state"]
    mlp = 3 * wide * hidden
    mamba = (2 * inner + 2 * gn + c["mamba_n_heads"]) * hidden \
        + inner * hidden
    attention = 2 * hidden * hidden \
        + 2 * c["num_key_value_heads"] * head * hidden
    kinds = layer_kinds(c)
    return kinds.count("mamba") * (mamba + mlp) \
        + kinds.count("attention") * (attention + mlp) \
        + c["vocab_size"] * hidden


def ssd_forward_flops(config, rows, tokens):
    """One layer's chunked scan, forward, its four products: C B^T inside a
    chunk (shared by a group's heads), the masked product with x, the
    chunk's states x^T B, and the carried state's read-out C S."""
    c = config
    chunk, heads, head = c["mamba_chunk_size"], c["mamba_n_heads"], \
        c["mamba_d_head"]
    groups, state = c["mamba_n_groups"], c["mamba_d_state"]
    positions = rows * tokens
    return 2 * positions * (chunk * groups * state        # C B^T
                            + chunk * heads * head        # (C B^T . L) x
                            + 2 * heads * head * state)   # states, read-out


def ssd_train_flops(config, rows, tokens):
    """All Mamba-2 layers' scans in one training step."""
    return 3 * layer_kinds(config).count("mamba") \
        * ssd_forward_flops(config, rows, tokens)


def ssd_train_bytes(config, rows, tokens, itemsize=2):
    """The least the scans of one training step move: forward reads x, B, C
    (``itemsize`` each) and dt (float32) and writes y; backward reads them
    and y's cotangent and writes the four gradients. No intermediate is
    counted: a fused kernel would keep them on the chip."""
    c = config
    inner = c["mamba_n_heads"] * c["mamba_d_head"]
    gn = c["mamba_n_groups"] * c["mamba_d_state"]
    positions = rows * tokens
    operands = positions * ((inner + 2 * gn) * itemsize
                            + c["mamba_n_heads"] * 4)
    result = positions * inner * itemsize
    forward = operands + result
    backward = operands + result + operands
    return layer_kinds(config).count("mamba") * (forward + backward)


def attention_train_flops(config, rows, tokens):
    """Scores and read-out of the causal attention layers, at half."""
    c = config
    width = c["hidden_size"]          # heads x head size
    forward = 2 * 2 * rows * tokens * tokens * width / 2
    return 3 * layer_kinds(c).count("attention") * forward


def train_flops(config, rows, tokens):
    """One training step over ``rows`` x ``tokens`` positions."""
    return 6 * matrix_parameters(config) * rows * tokens \
        + attention_train_flops(config, rows, tokens) \
        + ssd_train_flops(config, rows, tokens)
