"""A convolutional cell's device time by layer, in more detail than the five
readers of `benchmark/layer_index.py` give it:

    python -m benchmark.tests.layer_split --workload resnet50_fit --seed 7 \
        --seconds 40

Runs the cell through `benchmark/run.py` with ``--trace 1`` in this process
(the same account on standard error, the same line on standard output) and
prints one more line on standard error, ``bench: split {...}``, every time
in ms a step of the step program:

    op_ms, op_union_ms  the op events' durations summed (what the layers
                        tile) and their union, beside step_device_ms
    layers              convolution, batch_norm, pooling, None
    conv_forward_ms, conv_backward_ms, conv_unnamed_ms
                        the convolution layer's events by whether the
                        convolution they hold was traced under autodiff's
                        transpose (both gradients) or not (the forward),
                        or carries no ``op_name`` at all
    conv_with_bn_ms     the convolution layer's events whose fusion also
                        holds an instruction under ``batch_norm``
    conv_least_ms       the convolutions' FLOPs at the chip's peak
    bn_forward_ms, bn_backward_ms
                        the BatchNorm layer's events by ``transpose(`` in
                        their own ``op_name``
    unattributed_kinds  the five largest kinds of unattributed instructions
                        (name less its number, and category), with counts
    unattributed_top    the five largest unattributed instructions, with
                        their ``op_name``
    parse_s             `mxnet_tpu.shardprof`'s parse of each compiled
                        program's HLO text: [module, site, seconds]
On a machine with no TPU it needs ``--rehearse`` and then prints no split."""
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import flops, layer_index, reduce, run as harness  # noqa: E402


def _top(rows, n=5):
    return sorted(rows.items(), key=lambda kv: -kv[1][0])[:n]


def detail(run):
    """The split of the module docstring; None where `layer_index.events`
    finds nothing."""
    found = layer_index.events(run)
    if found is None:
        return None
    trace, work = run.trace_data, run.result["work"]
    steps = trace["dispatches"] * run.result["batches_per_dispatch"]

    def ms(events):
        return sum(e["end"] - e["start"] for e in events) / 1e6 / steps

    by_layer = {layer: [] for layer in layer_index.LAYERS + (None,)}
    for e, record in found:
        by_layer[layer_index.layer_of(record)].append((e, record))
    conv, bn = by_layer["convolution"], by_layer["batch_norm"]
    kinds, top = {}, {}
    for e, record in by_layer[None]:
        took = (e["end"] - e["start"]) / 1e6 / steps
        kind = "%s [%s]" % (re.sub(r"[.\d]+$", "", e["name"]), e["category"])
        row = kinds.setdefault(kind, [0.0, 0])
        row[0] += took
        row[1] += 1
        row = top.setdefault(e["name"], [0.0, record and record["op_name"]])
        row[0] += took
    events = [e for e, _ in found]
    lo, hi = trace["lo"], trace["hi"]
    union = reduce.union_ns([(e["start"], e["end"]) for e in events], lo, hi)
    seconds, runs = reduce.module_time(trace, trace["step_program"])
    out = {
        "steps": steps,
        "op_ms": ms(events),
        "op_union_ms": union / 1e6 / steps,
        "step_device_ms": seconds / (runs * run.result["batches_per_dispatch"])
        * 1e3 if runs else None,
        "layers": {str(k): ms([e for e, _ in v])
                   for k, v in by_layer.items()},
        "conv_forward_ms": ms([e for e, r in conv if r["conv"]
                               and "transpose(" not in r["conv"]]),
        "conv_backward_ms": ms([e for e, r in conv
                                if "transpose(" in r["conv"]]),
        "conv_unnamed_ms": ms([e for e, r in conv if r["conv"] == ""]),
        "conv_with_bn_ms": ms([e for e, r in conv
                               if "batch_norm" in r["scopes"]]),
        "bn_forward_ms": ms([e for e, r in bn
                             if "transpose(" not in r["op_name"]]),
        "bn_backward_ms": ms([e for e, r in bn
                              if "transpose(" in r["op_name"]]),
        "unattributed_kinds": _top(kinds),
        "unattributed_top": _top(top),
    }
    if run.peaks is not None:
        mats = flops.matmul_layers(work["layers"], work["shape"],
                                   work["layout"])
        peak = run.peaks["flops_per_s"][work["dtype"]] * run.cell["chips"]
        out["conv_least_ms"] = flops.train_flops(mats, op="conv") / peak * 1e3
    try:
        from mxnet_tpu import shardprof
        out["parse_s"] = [[e.get("module"), e["site"], e.get("parse_s")]
                          for e in shardprof.programs().values()]
    except ImportError:
        pass
    return out


def main(argv=None):
    real = harness.read_metrics

    def read_metrics(run, kind, entries):
        got = real(run, kind, entries)
        if kind == "layer_metrics":
            split = detail(run)
            if split is not None:
                harness.say("split " + json.dumps(split))
        return got

    harness.read_metrics = read_metrics
    return harness.main(list(sys.argv[1:] if argv is None else argv)
                        + ["--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
