"""A reading beside `benchmark/controls.py`'s, on the chip, not part of a run:
on the prompts and tokens a run kept, the share of routed choices (token,
expert layer, one of the experts per token) on which the plain reference and
the reference at a control's precision differ. `bf16` rounds the operands of
every product as the program's arithmetic does, so its share stands for the
program's, whose own choices do not leave the door.

    python -m benchmark.routed_choices --samples benchmark/out/served_sample_*.json
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from benchmark import harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--samples", nargs="+", required=True)
    ap.add_argument("--workload",
                    default="nemotron3_nano_30b_ep2.chat_saturated_s16")
    ap.add_argument("--requests", type=int, default=8)
    args = ap.parse_args(argv)
    harness.find_chip(1)
    import jax.numpy as jnp

    cfg = harness.load_cell(args.workload)["config_data"]
    reference = harness.load_by_name("reference", cfg["reference"])
    for path in args.samples:
        with open(path, encoding="utf-8") as f:
            kept = json.load(f)
        weights = reference.make_weights(kept["seed"] % harness.SEED_MOD, cfg)
        rows = [(r["prompt_ids"] + r["tokens"])
                for r in kept["requests"][:args.requests]]
        ids = np.zeros((len(rows), -(-max(map(len, rows)) // 128) * 128),
                       np.int32)
        for r, seq in enumerate(rows):
            ids[r, :len(seq)] = seq
        plain = reference.routed_choices(weights, jnp.asarray(ids), cfg)
        out = {"seed": kept["seed"], "tokens": sum(map(len, rows))}
        for precision in ("bf16", "int8w"):
            held = reference.at_precision(weights, precision)
            other = reference.routed_choices(held, jnp.asarray(ids), cfg)
            shares = []
            for a, b in zip(plain, other):  # one expert layer each
                differ = total = 0
                for r, seq in enumerate(rows):
                    for t in range(len(seq)):
                        differ += len(set(a[r, t]) - set(b[r, t]))
                        total += a.shape[-1]
                shares.append(differ / total)
            out[precision] = {"differ_share_by_layer": shares,
                              "differ_share": float(np.mean(shares))}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
