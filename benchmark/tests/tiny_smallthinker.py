"""The size the tests under ``benchmark/tests`` can hold on a CPU for the
SmallThinker cell: hidden 64, 4 query heads and 2 key-value heads of 16,
one period of four layers (full, then three windowed with a rotary term),
a window of 12 keys, 2 of the router's 8 experts held (experts 2 and 3),
3 a token, expert width 32, vocabulary 96, 2 rows of 32 tokens, float32,
laid over the cell ``smallthinker_fit`` by ``--rehearse``. The limits are
the tiny size's own: on the CPU the program and the float32 reference
agree to about 1e-6, the bfloat16 control reads 1e-4 and more, and the
faults more (readings on a CPU; no device number)."""
import json

CELL = "smallthinker_fit"

LIMITS = {"loss_1": 1e-5, "first_probs": 2e-5, "first_grad": 1e-3,
          "first_grad_median": 1e-3, "change_median": 1e-2}

CONFIG = dict(
    vocab_size=96, hidden_size=64, head_dim=16, num_attention_heads=4,
    num_key_value_heads=2, moe_num_primary_experts=2,
    moe_router_num_experts=8, moe_first_held_expert=2,
    moe_num_active_primary_experts=3, moe_ffn_hidden_size=32,
    rope_theta=10000.0, sliding_window_size=12, rows=2, tokens=32,
    dtype="float32")


def overlay():
    return json.dumps({
        "config": CONFIG,
        "traffic": {"pool_batches": 8, "trace_batches": 3,
                    "warmup_batches": 4},
        "limits": {"limits": LIMITS}})
