"""The least time the chip could take for the steps of one epoch program
(bound by compute at this batch: benchmark/ops/vit_train_step.py), over the
device time of one run of that program in the trace."""

from benchmark.layer_metrics import _shared
from benchmark.ops import vit_train_step


def read(result, cell, peaks):
    took = _shared.module_mean_s(result, "epoch_scan")
    if not took:
        return None
    cfg = {**cell["config_data"],
           "num_labels": cell["config_data"]["assumed"]["num_labels"]}
    least, _ = vit_train_step.least_seconds(
        cfg, cell["traffic_data"]["batch_size"], peaks)
    return 100.0 * least * result["steps_per_epoch"] / took
