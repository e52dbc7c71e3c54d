"""Builder: a Gluon model-zoo network as a symbol under SoftmaxOutput.

The zoo block is called on a symbol variable, which yields the very graph
`HybridBlock.export` writes (checked node for node against the export of
`chip_smoke.py`'s `_export_resnet`), without initializing 138 M parameters
on the host or writing files. The parameters are NOT the zoo's: the
benchmark draws them from the seed (`reference.make_params`) and hands the
same arrays to the program and to the plain reference.

A builder gives the entry:
  symbol(config)   -> the loss symbol; inputs ``data`` and ``softmax_label``
  aux_names(names) -> auxiliary states the program keeps beside the
                      reference's parameters, for the name check
"""
import json


def symbol(config):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    args = dict(config["builder_args"])
    model = args.pop("model")
    dropout = args.pop("dropout", None)
    net = vision.get_model(model, **args)
    # through JSON, as an export and load goes: the variables of a block
    # that was never initialized carry deferred shapes (a 0 for the input
    # channels) that the JSON does not, and bind infers them from the data
    graph = json.loads(net(mx.sym.Variable("data")).tojson())
    if dropout is not None:
        # the zoo fixes its dropout rate in code; the configuration states
        # its own, so it is written into the graph's Dropout nodes
        for node in graph["nodes"]:
            if node["op"] == "Dropout":
                node.setdefault("attrs", {})["p"] = repr(float(dropout))
    out = mx.sym.load_json(json.dumps(graph))
    return mx.sym.SoftmaxOutput(out, name="softmax")
