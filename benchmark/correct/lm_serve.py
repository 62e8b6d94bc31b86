"""What decides `correct` in a generate cell: a sample, drawn from the seed,
of the requests the window finished, with the longest in it, against the
full forward pass of the configuration's plain reference (`"reference"` in
its file) over each prompt with its served tokens.

The door gives tokens, not logits, and with random weights the largest logit
changes on rounding. So what is read, for every served token of the sample,
is how far the reference's logit of that token lies below the reference's
best at that position (0 where the served token is the reference's best). It
is valid for greedy tokens, which is what the traffic sends. The number
compared is `served_gap_mean`, the mean of that gap over the sample's tokens:
the widest gap was read first and did not separate the program from its
control by three times (PERF.md, section 6), and is kept in `info`. A token
altered where it is produced reads as a gap of the size of the logits.
"""

from __future__ import annotations

import json
import os

import numpy as np

from benchmark import harness


def sample(records: list, seed: int, count: int) -> list:
    """`count` finished requests: the longest, and the rest drawn from the
    seed."""
    done = [r for r in records if not r["error"] and r["done"] is not None
            and r["tokens"]]
    if not done:
        return []
    done.sort(key=lambda r: r["i"])
    longest = max(done, key=lambda r: r["prompt_tokens"] + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 2])
    picks = rng.choice(len(rest), size=min(count - 1, len(rest)),
                       replace=False) if rest else []
    return [longest] + [rest[int(i)] for i in picks]


def served_gaps(cfg: dict, seed31: int, picked: list) -> np.ndarray:
    reference = harness.load_by_name("reference", cfg["reference"])
    weights = reference.make_weights(seed31, cfg)
    requests = [(r["prompt_ids"], r["tokens"]) for r in picked]
    logits = reference.served_logits(weights, cfg, requests)
    return reference.token_gaps(logits, [t for _, t in requests])


def judge(cfg: dict, gaps: np.ndarray | None) -> dict:
    """The numbers compared, each beside the configuration's limit. The
    run's own tokens and a control's go through this same function."""
    value = float(gaps.mean()) if gaps is not None and gaps.size \
        else float("inf")
    return {"served_gap_mean": {"value": value,
                                "limit": cfg["limits"]["served_gap_mean"]}}


def check(cell: dict, ctx, result: dict) -> dict:
    cfg, traffic = cell["config_data"], cell["traffic_data"]
    picked = sample(result["records"], ctx.seed, traffic["check_requests"])
    if not picked:
        return judge(cfg, None)
    # kept for benchmark/controls.py, which reads the control on the same
    # prompts and tokens
    with open(os.path.join(ctx.out_dir, f"served_sample_{ctx.seed}.json"),
              "w", encoding="utf-8") as f:
        json.dump({"seed": ctx.seed, "requests": [
            {"prompt_ids": r["prompt_ids"], "tokens": r["tokens"]}
            for r in picked]}, f)
    gaps = served_gaps(cfg, ctx.seed % harness.SEED_MOD, picked)
    result["check_info"] = {
        "requests": len(picked), "tokens": int(gaps.size),
        "tokens_off_best": int((gaps > 0).sum()),
        "served_gap_max": float(gaps.max())}
    return judge(cfg, gaps)
