"""The whole training step's share of the chip's peak: the step's operations
(benchmark/ops/vit_train_step.py) times the steps of the window's epochs,
over the sum of their logged `epoch_time` times the bf16 peak. The epoch is
fenced: the trainer fetches the losses before it logs."""

from benchmark.layer_metrics import _shared
from benchmark.ops import vit_train_step


def read(result, cell, peaks):
    epochs = _shared.window_epochs(result)
    seconds = sum(e["epoch_time"] for e in epochs)
    if not seconds:
        return None
    cfg = {**cell["config_data"],
           "num_labels": cell["config_data"]["assumed"]["num_labels"]}
    flops = vit_train_step.flops(cfg, cell["traffic_data"]["batch_size"])
    steps = len(epochs) * result["steps_per_epoch"]
    return 100.0 * flops * steps / (seconds * peaks["bf16_flops_per_s"])
