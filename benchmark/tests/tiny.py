"""The CPU rehearsal's cells: the committed configurations and traffic files
with their sizes made tiny, run through the same functions as on the chip.
Nothing a rehearsal reads is a measurement, and it never prints the
contract's line as one."""

from __future__ import annotations

import copy
import time

from benchmark import harness
from benchmark.harness import Context

CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
             "hbm_bytes": 1e10}


def cell(workload: str, **traffic_changes) -> dict:
    """`workload` of BENCHMARK.json at a tiny size: the `tiny` of its
    configuration file (`sizes` over the published keys, `limits` for the
    limits) and of its traffic file (over the file's own keys)."""
    c = copy.deepcopy(harness.load_cell(workload))
    small = c["config_data"].pop("tiny")
    c["config_data"].update(small["sizes"], limits=small["limits"])
    c["traffic_data"].update(c["traffic_data"].pop("tiny"))
    c["traffic_data"].update(traffic_changes)
    return c


def cases() -> list:
    """Every cell of BENCHMARK.json with the faults its configuration's
    template knows: what the rehearsals and the broken paths run over."""
    names = [w["name"] for w in harness.load_benchmark()["workloads"]]
    return [(name, harness.load_cell(name)["config_data"]["tiny"]["faults"])
            for name in names]


def context(out_dir: str, seed: int = 5, seconds: float = 3.0,
            trace: bool = False) -> Context:
    import jax

    return Context(devices=jax.devices()[:1], peaks=CPU_PEAKS,
                   meter=harness.CompileMeter(), seed=seed, seconds=seconds,
                   trace=trace, out_dir=out_dir, t_start=time.time())
