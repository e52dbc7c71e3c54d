"""Builder: the program's own Moonlight symbol
(`mxnet_tpu.models.moonlight`), in the configuration's precision and with
its recomputation. The benchmark only calls it; the parameters are NOT the
program's: they come from the seed (`refs/moonlight.init_params`) and go to
the program and to the plain reference alike. The selection biases are the
module's auxiliary states, zeros at the start as in the reference."""


def symbol(config):
    from mxnet_tpu.models import moonlight
    return moonlight.symbol(config, dtype=config["dtype"],
                            recompute=config["recompute"])
