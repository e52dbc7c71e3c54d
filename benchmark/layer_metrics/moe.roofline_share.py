"""Layer: graph and kernels. The held experts' grouped products' share of
their roofline: the least time the chip could take for them (the larger of
their counted operations over the peak and their least bytes over the
memory's rate, `flops_moe.py`) over the device time of the kernels XLA
lowers them to and the events under ``moe_experts`` (`grouped.py`).
The count takes the EXPECTED assignments (tokens x top_k x held / router
width): a seeded router spreads the tokens near evenly, so a step routes
that many to the held experts within a few hundredths. Products that ran
over a worst-case buffer rather than the rows routed read low here.
Recomputed passes take time and are not credited."""
from benchmark import flops_moe, grouped


def read(run):
    took = grouped.device_ms_per_step(run, "moe_experts")
    work = run.result["work"]
    if took is None or run.peaks is None or "config" not in work:
        return None
    cfg, rows, tokens = work["config"], work["rows"], work["tokens"]
    least = flops_moe.least_seconds(
        flops_moe.experts_train_flops(cfg, rows, tokens),
        flops_moe.experts_train_bytes(cfg, rows, tokens), cfg, run.peaks)
    return least / (took / 1e3) * 100.0
