"""Builder: the program's own SmallThinker symbol
(`mxnet_tpu.models.smallthinker`), in the configuration's precision and
with its recomputation. The benchmark only calls it; the parameters are NOT
the program's: they come from the seed (`refs/smallthinker.init_params`)
and go to the program and to the plain reference alike."""


def symbol(config):
    from mxnet_tpu.models import smallthinker
    return smallthinker.symbol(config, dtype=config["dtype"],
                               recompute=config["recompute"])
