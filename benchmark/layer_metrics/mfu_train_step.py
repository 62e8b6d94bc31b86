"""The whole training step's share of the chip's peak: the step's operations
(the configuration's `ops.train_step` module) times the steps of the
window's epochs, over the sum of their logged `epoch_time` times the bf16
peak. The epoch is fenced: the trainer fetches the losses before it logs."""

from benchmark import harness
from benchmark.layer_metrics import _shared


def read(result, cell, peaks):
    epochs = _shared.window_epochs(result)
    seconds = sum(e["epoch_time"] for e in epochs)
    if not seconds:
        return None
    cfg = cell["config_data"]
    ops = harness.load_by_name("ops", cfg["ops"]["train_step"])
    flops = ops.flops(cfg, cell["traffic_data"]["batch_size"])
    steps = len(epochs) * result["steps_per_epoch"]
    return 100.0 * flops * steps / (seconds * peaks["bf16_flops_per_s"])
