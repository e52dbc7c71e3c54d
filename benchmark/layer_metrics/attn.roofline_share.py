"""Layer: graph and kernels. Attention's kernels' share of their roofline:
the least time the chip could take for the scores and read-out over the
(query, key) pairs each layer's mask admits (causal, or a sliding window),
forward and both gradients, or for the least bytes they move (`flops_moe.py`),
whichever is longer, over the device time under ``flash_attention``.
Recomputed passes take time and are not credited; a window whose blocks
were computed and then thrown away reads low here."""
from benchmark import flops_moe, scopes


def read(run):
    took = scopes.device_ms_per_step(run, "flash_attention")
    work = run.result["work"]
    if took is None or run.peaks is None or "config" not in work:
        return None
    cfg, rows, tokens = work["config"], work["rows"], work["tokens"]
    least = flops_moe.least_seconds(
        flops_moe.attention_train_flops(cfg, rows, tokens),
        flops_moe.attention_train_bytes(cfg, rows, tokens), cfg, run.peaks)
    return least / (took / 1e3) * 100.0
