"""Seconds of the serving worker's `setup.load` span (`worker/inference.py`
`_load_model`: the parameters' artifact read, `load_params`, the template's
import and `load_parameters`): the `_sum` of
`rafiki_worker_phase_seconds{phase="setup.load"}`. None where the program
has no such span or no compile record of the set-up (`_setup.py`)."""

from benchmark.layer_metrics import _setup


def read(result, cell, peaks):
    if _setup.setup_events(result) is None:
        return None
    return _setup.phase_seconds().get("setup.load")
