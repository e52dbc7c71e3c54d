"""Device time by the program's named scopes (`jax.named_scope`,
`mx.AttrScope(profiler_scope=...)`). A device trace names an ``XLA Ops``
event by its HLO instruction (``%fusion.1437 = ...``) and leaves the
instruction's metadata out (the tracer runs with ``enable_hlo_proto`` off,
see `reduce.py`), so the scope comes from the step program's own compiled
HLO text, which the entry hands over as ``result["work"]["hlo_text"]``:
there every instruction carries the ``op_name`` it was traced under,
forward, recomputed or in the backward pass alike
(``.../transpose(jvp(mamba2_ssd))/...``). A fusion counts under its own
``op_name``, which XLA takes from the instruction it was built around.

Instruction names are unique within one program only (every program has a
``fusion.3``), so an event is looked up by name only if it starts inside an
execution of the step program (the trace's ``XLA Modules`` line, the mix's
``step_program``): the core runs one program at a time, and what another
program (the metric's reduction, staging) runs between two steps is not the
step's."""
import bisect
import re


INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*op_name="([^"]*)"', re.M)


def op_names(hlo_texts):
    """{instruction name: op_name} over the texts of a program's
    executables."""
    out = {}
    for text in hlo_texts:
        out.update(INSTRUCTION.findall(text))
    return out


def in_step_program(trace):
    """A test of an op event of the first chip: does it start inside an
    execution of the step program?"""
    rx = re.compile(trace["step_program"])
    runs = sorted((m["start"], m["end"])
                  for m in trace["chips"][0]["modules"]
                  if rx.search(m["name"]))
    starts = [start for start, _ in runs]

    def inside(event):
        i = bisect.bisect_right(starts, event["start"]) - 1
        return i >= 0 and event["start"] < runs[i][1]

    return inside


def device_ms_per_step(run, scope):
    """Device milliseconds a step of the first chip's op events that ran
    inside the step program and whose instruction was traced under
    ``scope`` (a path component of ``op_name``, bare or inside ``jvp(...)``
    / ``transpose(...)``), over the traced stretch; None where there is no
    trace, no HLO text or no such event."""
    trace, work = run.trace_data, run.result["work"]
    if trace is None or not work.get("hlo_text") or not trace["dispatches"]:
        return None
    if "_op_names" not in work:
        work["_op_names"] = op_names(work["hlo_text"])
    rx = re.compile(r"(^|[/(])%s([/)]|$)" % re.escape(scope))
    under = {name for name, path in work["_op_names"].items()
             if rx.search(path)}
    inside = in_step_program(trace)
    hits = [e["end"] - e["start"] for e in trace["chips"][0]["ops"]
            if e["name"] in under and inside(e)
            and e["category"] not in ("while", "conditional", "call")]
    if not hits:
        return None
    steps = trace["dispatches"] * run.result["batches_per_dispatch"]
    return sum(hits) / 1e9 / steps * 1e3
