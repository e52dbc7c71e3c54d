"""Layer: graph and kernels. The state-space scans' share of their
roofline: the least time the chip could take for them (the larger of their
counted operations over the peak and their least bytes over the memory's
rate, `flops_tokens.py`) over the device time under ``mamba2_ssd``.
Recomputed passes take time and are not credited."""
from benchmark import flops_tokens, scopes


def read(run):
    took = scopes.device_ms_per_step(run, "mamba2_ssd")
    work = run.result["work"]
    if took is None or run.peaks is None or "config" not in work:
        return None
    cfg, rows, tokens = work["config"], work["rows"], work["tokens"]
    least = max(
        flops_tokens.ssd_train_flops(cfg, rows, tokens)
        / run.peaks["flops_per_s"][cfg["dtype"]],
        flops_tokens.ssd_train_bytes(cfg, rows, tokens)
        / run.peaks["hbm_bytes_per_s"])
    return least / (took / 1e3) * 100.0
