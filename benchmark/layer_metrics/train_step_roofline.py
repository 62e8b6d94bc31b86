"""The least time the chip could take for the steps of one epoch program
(the configuration's `ops.train_step` module says which peak bounds it),
over the device time of one run of that program in the trace."""

from benchmark import harness
from benchmark.layer_metrics import _shared


def read(result, cell, peaks):
    took = _shared.module_mean_s(result, "epoch_scan")
    if not took:
        return None
    cfg = cell["config_data"]
    ops = harness.load_by_name("ops", cfg["ops"]["train_step"])
    least, _ = ops.least_seconds(cfg, cell["traffic_data"]["batch_size"],
                                 peaks)
    return 100.0 * least * result["steps_per_epoch"] / took
