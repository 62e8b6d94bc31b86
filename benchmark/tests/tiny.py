"""The CPU rehearsal's cells: the committed configurations and traffic files
with their sizes made tiny, run through the same functions as on the chip.
Nothing a rehearsal reads is a measurement, and it never prints the
contract's line as one."""

from __future__ import annotations

import copy
import time

from benchmark import harness
from benchmark.harness import Context

TINY_VIT = {"hidden_size": 64, "num_hidden_layers": 2,
            "num_attention_heads": 4, "intermediate_size": 256,
            "patch_size": 4, "image_size": 32}
TINY_LM = {"n_embd": 64, "n_layer": 2, "n_head": 4, "n_positions": 128,
           "n_ctx": 128, "vocab_size": 512}
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
             "hbm_bytes": 1e10}


def cell(workload: str, **traffic_changes) -> dict:
    """`workload` of BENCHMARK.json at a tiny size."""
    c = copy.deepcopy(harness.load_cell(workload))
    if c["config"] == "vit_b16":
        c["config_data"].update(TINY_VIT)
        c["traffic_data"].update(n_train=32, n_test=16, batch_size=8,
                                 epochs=2, trace_seconds=2)
        c["config_data"]["limits"] = {
            "loss_first_epoch_rel": 0.003, "change_worst_leaf_rel": 0.008,
            "change_median_leaf_rel": 0.002}
    else:
        c["config_data"].update(TINY_LM)
        for key in ("prompt_tokens", "answer_tokens"):
            c["traffic_data"][key] = {"mean": 13, "sigma": 0.5, "min": 4,
                                      "max": 40}
        c["traffic_data"]["answer_tokens"]["max"] = 16
        c["traffic_data"].update(shapes=8, check_requests=4, trace_seconds=1)
        c["traffic_data"]["settings"] = {
            "budget": {}, "env": {"RAFIKI_GEN_PREFILL_CHUNK": "16"}}
        c["config_data"]["limits"] = {"served_gap_mean": 1e-3}
    c["traffic_data"].update(traffic_changes)
    return c


def context(out_dir: str, seed: int = 5, seconds: float = 3.0,
            trace: bool = False) -> Context:
    import jax

    return Context(devices=jax.devices()[:1], peaks=CPU_PEAKS,
                   meter=harness.CompileMeter(), seed=seed, seconds=seconds,
                   trace=trace, out_dir=out_dir, t_start=time.time())
