"""The size the tests under ``benchmark/tests`` can hold on a CPU: the zoo's
vgg16 at 32x32, batch 8, ten classes, in float32, laid over the cell
``vgg16_fit`` by ``--rehearse``. The limits are the tiny size's own: on the
CPU the program and the float32 reference agree to 1e-6 (first_grad 1e-7,
change 3e-4 at worst), the bfloat16 control reads first_probs 4.6e-3,
first_grad 3.3e-2, change 3.1e-2, and the faults more (readings on this
sandbox's CPU, PR 23; no device number)."""
import json

CELL = "vgg16_fit"

LIMITS = {"loss_1": 1e-4, "first_probs": 1e-3, "first_grad": 5e-3,
          "change": 5e-3, "change_median": 1e-3}


def overlay(batches_per_dispatch=1):
    return json.dumps({
        "config": {
            "dtype": "float32", "batch": 8, "image": 32, "classes": 10,
            "builder_args": {"model": "vgg16", "classes": 10, "dropout": 0.0,
                             "prefix": "vgg0_"},
            "reference_args": {
                "prefix": "vgg0_", "stack_convs": [2, 2, 3, 3, 3],
                "stack_channels": [64, 128, 256, 512, 512], "hidden": 4096,
                "dropout": 0.0, "classes": 10}},
        "traffic": {"pool_batches": 8, "trace_batches": 3,
                    "batches_per_dispatch": batches_per_dispatch,
                    "warmup_batches": 4},
        "limits": {"limits": {
            k: v for k, v in LIMITS.items()
            if batches_per_dispatch == 1 or k != "first_grad"}},
    })
