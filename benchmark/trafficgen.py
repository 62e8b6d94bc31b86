"""The one generator every traffic file is read by. A traffic file holds
parameters (lengths, callers); this turns them and `--seed` into the inputs
of a run. The seed must not change the work, only its order: lengths are the
quantiles of the file's log-normal, a block of `shapes` of them, paired once
by the file's own `shape_seed`; a run sends block after block, each in an
order drawn from the run's seed, so every seed offers the same set of sizes
in another order, and a window that holds several blocks serves the same
work whatever the seed. Token ids (and the weights) come from the run's
seed. With 64 independent draws to a window two seeds read 40.2 and 36.4
tokens/s (my chip run, PR 23): the blocks are what keeps that out.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def images(seed: int, n: int, image: int, channels: int = 3,
           classes: int = 10):
    """Seeded images with a learnable per-class offset (chip_smoke.py's)."""
    rng = np.random.default_rng(seed)
    y = rng.integers(0, classes, size=n).astype(np.int32)
    x = rng.normal(size=(n, image, image, channels)).astype(np.float32)
    x += (y[:, None, None, None].astype(np.float32) - classes / 2) * 0.25
    return x, y


def _quantile_lengths(mu: float, spec: dict, n: int) -> list:
    return [int(min(max(round(math.exp(
        mu + spec["sigma"] * NormalDist().inv_cdf((i + 0.5) / n))),
        spec["min"]), spec["max"])) for i in range(n)]


def lognormal_quantiles(spec: dict, n: int) -> list:
    """n lengths at the (i + 1/2) / n quantiles of a log-normal with the
    file's sigma, clipped to [min, max]. The file gives the `mean`, which is
    what the public traces publish: the median is the one at which the n
    clipped lengths have that mean."""
    low, high = math.log(spec["min"]), math.log(spec["max"])
    for _ in range(60):
        mu = (low + high) / 2
        lengths = _quantile_lengths(mu, spec, n)
        low, high = (mu, high) if sum(lengths) / n < spec["mean"] \
            else (low, mu)
    return lengths


def shapes(traffic: dict) -> list:
    """The file's set of (prompt tokens, answer tokens), the same for every
    seed."""
    n = traffic["shapes"]
    prompts = lognormal_quantiles(traffic["prompt_tokens"], n)
    answers = lognormal_quantiles(traffic["answer_tokens"], n)
    pairing = np.random.default_rng(traffic["shape_seed"]).permutation(n)
    return [(prompts[i], answers[int(pairing[i])]) for i in range(n)]


def request_stream(traffic: dict, seed: int, vocab: int, count: int) -> list:
    """`count` requests: the set of shapes, block after block, each block in
    an order drawn from the run's seed; each use with fresh token ids from
    the run's seed (ids never repeat a prompt, so the prefix cache finds
    nothing to share)."""
    rng = np.random.default_rng(seed)
    order = np.random.default_rng([seed, 1])
    pairs = shapes(traffic)
    out = []
    while len(out) < count:
        for i in order.permutation(len(pairs)):
            prompt, answer = pairs[int(i)]
            out.append({"prompt_ids": rng.integers(
                0, vocab, size=prompt).tolist(), "max_tokens": answer})
    return out[:count]
