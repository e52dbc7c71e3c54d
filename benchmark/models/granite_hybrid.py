"""Builder: the program's own Granite-4.0-H symbol
(`mxnet_tpu.models.granite_hybrid`), in the configuration's precision and
with its recomputation. The benchmark only calls it; the parameters are NOT
the program's: they come from the seed (`refs/granite_hybrid.init_params`)
and go to the program and to the plain reference alike."""


def symbol(config):
    from mxnet_tpu.models import granite_hybrid
    return granite_hybrid.symbol(config, dtype=config["dtype"],
                                 recompute=config["recompute"])
