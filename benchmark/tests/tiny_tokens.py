"""The size the tests under ``benchmark/tests`` can hold on a CPU for the
token cell: hidden 64, 4 heads of 16 (2 key-value heads), Mamba-2 8 heads
of 16 with state 16 and chunks of 8, layers mamba / attention / mamba,
vocabulary 96, 2 rows of 32 tokens, float32, laid over the cell
``granite_h_micro_fit`` by ``--rehearse``. The limits are the tiny size's
own: on the CPU the program and the float32 reference agree to 1e-6, the
bfloat16 control reads first_probs 1e-4, first_grad_median 5e-3 and
change_median 6e-2, and the faults more (readings on this sandbox's CPU,
PR 33; no device number)."""
import json

CELL = "granite_h_micro_fit"

LIMITS = {"loss_1": 1e-5, "first_probs": 2e-5, "first_grad": 1e-3,
          "first_grad_median": 1e-3, "change_median": 1e-2}

CONFIG = dict(
    vocab_size=96, hidden_size=64, shared_intermediate_size=128,
    num_attention_heads=4, num_key_value_heads=2, mamba_expand=2,
    mamba_n_heads=8, mamba_d_head=16, mamba_n_groups=1, mamba_d_state=16,
    mamba_d_conv=4, mamba_chunk_size=8,
    layer_types=["mamba", "attention", "mamba"], num_hidden_layers=3,
    attention_multiplier=1 / 16, rows=2, tokens=32, dtype="float32")


def overlay():
    return json.dumps({
        "config": CONFIG,
        "traffic": {"pool_batches": 8, "trace_batches": 3,
                    "warmup_batches": 4},
        "limits": {"limits": LIMITS}})
