"""The size the tests under ``benchmark/tests`` can hold on a CPU for the
Moonlight cell: hidden 64, 4 heads of 16 + 8 query-key dims and 16 value
dims over a latent of 32, one dense layer (width 48) and two expert
layers, 4 of the router's 8 experts held (experts 2-5), 2 a token, expert
width 16 with two shared, vocabulary 256, 2 rows of 64 tokens, float32,
laid over the cell ``moonlight_fit`` by ``--rehearse``. The limits are the
tiny size's own: on the CPU the program and the float32 reference agree to
about 1e-6, the bfloat16 control reads 1e-4 and more, and the faults more
(readings on a CPU; no device number)."""
import json

CELL = "moonlight_fit"

LIMITS = {"loss_1": 1e-5, "first_probs": 2e-5, "first_grad": 1e-3,
          "first_grad_median": 1e-3, "change_median": 1e-2}

CONFIG = dict(
    vocab_size=256, hidden_size=64, num_attention_heads=4,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
    intermediate_size=48, moe_intermediate_size=16, n_routed_experts=4,
    router_num_experts=8, first_held_expert=2, num_experts_per_tok=2,
    n_shared_experts=2, num_hidden_layers=3, rope_theta=10000.0, rows=2,
    tokens=64, dtype="float32")


def overlay():
    return json.dumps({
        "config": CONFIG,
        "traffic": {"pool_batches": 8, "trace_batches": 3,
                    "warmup_batches": 4},
        "limits": {"limits": LIMITS}})
