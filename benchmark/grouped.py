"""Device time of a sparse-expert layer, grouped products included. XLA
lowers `jax.lax.ragged_dot` on the TPU to kernels of its own: one that
lays out the groups' tiles (``ragged-dot-metadata``) and the grouped
products (``ragged-dot-none``), instructions whose metadata names them and
keeps no ``op_name``, so the scope the program traced them under
(``moe_experts``, inside ``moe``) is lost and `scopes.device_ms_per_step`
does not see them. They are found here by their instruction's name, in the
step program only (`scopes.in_step_program`), and added to the events that
carry the scope."""
import re

from benchmark import scopes

KERNELS = re.compile(r"^ragged-dot[\w\-]*(\.\d+)?$")


def device_ms_per_step(run, scope):
    """As `scopes.device_ms_per_step`, with the grouped-product kernels
    counted under ``scope``; None where there is no trace, no HLO text or
    no such event."""
    trace, work = run.trace_data, run.result["work"]
    if trace is None or not work.get("hlo_text") or not trace["dispatches"]:
        return None
    if "_op_names" not in work:
        work["_op_names"] = scopes.op_names(work["hlo_text"])
    rx = re.compile(r"(^|[/(])%s([/)]|$)" % re.escape(scope))
    under = {name for name, path in work["_op_names"].items()
             if rx.search(path)}
    inside = scopes.in_step_program(trace)
    hits = [e["end"] - e["start"] for e in trace["chips"][0]["ops"]
            if (e["name"] in under or KERNELS.match(e["name"]))
            and inside(e)
            and e["category"] not in ("while", "conditional", "call")]
    if not hits:
        return None
    steps = trace["dispatches"] * run.result["batches_per_dispatch"]
    return sum(hits) / 1e9 / steps * 1e3
