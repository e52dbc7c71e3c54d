"""Layer: graph and kernels. Latent attention's kernels' share of their
roofline: the least time the chip could take for the scores (192-wide
queries and keys) and the read-out (128-wide values) over the causal
(query, key) pairs, forward and both gradients, or for the least bytes
they move (`flops_mla.py`), whichever is longer, over the device time
under ``flash_attention``. Recomputed passes take time and are not
credited."""
from benchmark import flops_mla, scopes


def read(run):
    took = scopes.device_ms_per_step(run, "flash_attention")
    work = run.result["work"]
    if took is None or run.peaks is None or "config" not in work:
        return None
    cfg, rows, tokens = work["config"], work["rows"], work["tokens"]
    least = flops_mla.least_seconds(
        flops_mla.attention_train_flops(cfg, rows, tokens),
        flops_mla.attention_train_bytes(cfg, rows, tokens), cfg, run.peaks)
    return least / (took / 1e3) * 100.0
