"""Device time of the convolutional cells by layer: convolution, BatchNorm,
pooling and what none of them claims.

The trace names an ``XLA Ops`` event by its HLO instruction and leaves the
instruction's ``op_name`` out (`reduce.py`). The program keeps, for every
program it compiles, an index of its instructions
(`mxnet_tpu.shardprof.op_index`, built from the compiled HLO text at the
compile, inside set-up): each instruction's ``op_name``, the scope path
components of what it holds (a fusion's: of every instruction fused into
it) and whether it is, or holds, a convolution traced under the scope
``convolution`` (`mxnet_tpu/ops/nn.py` names ``convolution``,
``batch_norm`` and ``pooling``). This file takes the index of the step
program, the module that the mix's ``step_program`` matches on the trace's
``XLA Modules`` line, and gives each op event that starts inside an
execution of that program (`scopes.in_step_program`) to one layer, by
precedence:

    convolution  the instruction is a convolution, or a fusion holding one
                 under ``convolution`` (or one with no ``op_name``, which a
                 compiler pass rebuilt), whatever else is fused into it
    batch_norm   it holds an instruction under ``batch_norm``
    pooling      it holds an instruction under ``pooling``
    None         none of these: unattributed

An event whose instruction is not in the index is unattributed too. Where
the program keeps no index (``MXNET_SHARDPROF=0``, or a program from
before the index) or there is no trace, every reader gets None."""
import re

from benchmark import scopes

LAYERS = ("convolution", "batch_norm", "pooling")
WRAPPERS = ("while", "conditional", "call")   # their bodies' ops are events


def step_index(run):
    """The op index of the step program, or None."""
    trace = run.trace_data
    if trace is None or not trace["dispatches"]:
        return None
    try:
        from mxnet_tpu.shardprof import op_index
    except ImportError:
        return None
    rx = re.compile(trace["step_program"])
    modules = {m["name"].rsplit("(", 1)[0]
               for m in trace["chips"][0]["modules"] if rx.search(m["name"])}
    for module in sorted(modules):
        ops = op_index(module)
        if ops:
            return ops
    return None


def layer_of(record):
    """The layer of one index record (None: unattributed)."""
    if record is None:
        return None
    if record["conv"] is not None:
        return "convolution"
    for layer in LAYERS[1:]:
        if layer in record["scopes"]:
            return layer
    return None


def events(run):
    """[(op event, its index record or None)] of the first chip's op events
    that start inside an execution of the step program, wrappers left out;
    None where there is no trace or no index."""
    ops = step_index(run)
    if ops is None:
        return None
    inside = scopes.in_step_program(run.trace_data)
    return [(e, ops.get(e["name"])) for e in run.trace_data["chips"][0]["ops"]
            if e["category"] not in WRAPPERS and inside(e)]


def split(run):
    """{layer or None: device ms a step}, every layer of `LAYERS` and None
    present; None where `events` is None. Kept on ``run.result["work"]``,
    for the five readers."""
    work = run.result["work"]
    if "_layer_split" not in work:
        found = events(run)
        out = None
        if found is not None:
            steps = run.trace_data["dispatches"] \
                * run.result["batches_per_dispatch"]
            out = dict.fromkeys(LAYERS + (None,), 0.0)
            for e, record in found:
                out[layer_of(record)] += (e["end"] - e["start"]) / 1e6 / steps
        work["_layer_split"] = out
    return work["_layer_split"]


def device_ms(run, layer):
    """Device ms a step attributed to ``layer``; None where nothing is."""
    got = split(run)
    if got is None or not got[layer]:
        return None
    return got[layer]
