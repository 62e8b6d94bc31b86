"""The readings the limits of `correct` are set from, taken on the chip at
the cells' own sizes. Not part of a benchmark run.

    python -m benchmark.controls vit --seeds 11 12 13 [--workload <cell>]
    python -m benchmark.controls lm --samples benchmark/out/served_sample_*.json

`vit`: for each seed the reference trial (float32, `highest`), then put in
the program's place the control (float8) and the half-batch fault. A step
that returns its state unchanged reads 1 by that measure and needs no run.
`lm`: for each sample a run kept (the prompts and the tokens the program
served), the token that each lower precision puts first at every position,
beside the program's own tokens.
The cell is `--workload`'s; its configuration file names the family and
the reference. Each reading goes through the family's own `judge()` with the
cell's limits, as a run's numbers do, and is printed with the `correct` it
would get: the control (`control_fp8`, `int8w`) and the fault have to read
false. `bf16` is read beside them: the configuration says why it is no step
down.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from benchmark import harness, trafficgen


def _parts(workload: str) -> tuple:
    """The cell, its family's comparison and its plain reference."""
    cell = harness.load_cell(workload)
    cfg = cell["config_data"]
    return (cell, harness.load_by_name("correct", cfg["family"]),
            harness.load_by_name("reference", cfg["reference"]))


def vit(workload: str, seeds: list, lrs: list) -> None:
    cell, family, reference = _parts(workload)
    cfg = family.reference_cfg(cell["config_data"])
    traffic = cell["traffic_data"]
    sizes = harness.template_values(cfg)
    for seed, lr in [(s, r) for s in seeds for r in lrs]:
        x, y = trafficgen.images(seed, traffic["n_train"], sizes["IMAGE"],
                                 sizes["CHANNELS"], sizes["CLASSES"])
        args = (seed % harness.SEED_MOD, cfg, x, y, lr,
                traffic["batch_size"], traffic["epochs"])
        ref = reference.train(*args)
        out = {"seed": seed, "lr": lr,
               "ref_epoch_losses": ref["epoch_losses"]}
        for name, kw in (("control_fp8", {"quant": "fp8"}),
                         ("fault_half_batch", {"fault": "half_batch"})):
            other = reference.train(*args, **kw)
            checks = family.judge(cfg, family.compare(
                {"epoch_losses": other["epoch_losses"],
                 "change_norm": other["change_norm"]}, ref))
            out[name] = {"correct": harness.within_limits(checks),
                         "checks": checks}
            out[name + "_epoch_losses"] = other["epoch_losses"]
        print(json.dumps(out), flush=True)


def lm(workload: str, paths: list) -> None:
    import jax

    cell, family, reference = _parts(workload)
    cfg = cell["config_data"]
    for path in paths:
        with open(path, encoding="utf-8") as f:
            kept = json.load(f)
        requests = [(r["prompt_ids"], r["tokens"]) for r in kept["requests"]]
        weights = reference.make_weights(kept["seed"] % harness.SEED_MOD, cfg)
        ref_logits = reference.served_logits(weights, cfg, requests)
        gaps = reference.token_gaps(ref_logits, [t for _, t in requests])
        out = {"seed": kept["seed"], "tokens": int(gaps.size)}
        readings = {"program": gaps}
        for precision in ("bf16", "int8w"):
            held = reference.at_precision(weights, precision)
            logits = reference.served_logits(held, cfg, requests)
            first = [np.argmax(a, axis=-1) for a in logits]
            readings[precision] = reference.token_gaps(ref_logits, first)
            del held
        for name, read in readings.items():
            checks = family.judge(cfg, read)
            out[name] = {"correct": harness.within_limits(checks),
                         "checks": checks, "gap_max": float(read.max()),
                         "off_best": int((read > 0).sum())}
        print(json.dumps(out), flush=True)
        del weights
        jax.clear_caches()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="what", required=True)
    a = sub.add_parser("vit")
    a.add_argument("--seeds", type=int, nargs="+", required=True)
    a.add_argument("--lr", type=float, nargs="+", default=[3e-4])
    a.add_argument("--workload", default="vit_b16.hpo_search")
    b = sub.add_parser("lm")
    b.add_argument("--samples", nargs="+", required=True)
    b.add_argument("--workload", default="gpt2_large.chat_saturated")
    args = ap.parse_args(argv)
    harness.find_chip(1)
    from rafiki_tpu.sdk import compile_cache

    compile_cache.enable()
    if args.what == "vit":
        vit(args.workload, args.seeds, args.lr)
    else:
        lm(args.workload, args.samples)
    return 0


if __name__ == "__main__":
    sys.exit(main())
