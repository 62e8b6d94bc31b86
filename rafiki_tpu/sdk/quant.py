"""Weight-only int8 quantization for the serving path.

Small-batch serving on TPU is weight-bandwidth-bound: each predict batch
streams every kernel out of HBM while the MXU idles. Storing kernels as
int8 with per-output-channel scales halves that traffic (f32 masters ->
1 byte + one f32 scale per channel); the dequantize happens INSIDE the
jitted predict, where XLA fuses it into the consuming matmul/conv, so
activations and accumulation keep their usual dtype and only the
weight-side memory format changes. On v5e the int8 path also unlocks the
2x int8 MXU rate when XLA chooses to use it; correctness is what this
module guarantees (per-channel symmetric round-to-nearest, max |error|
scale/2 per weight), and is CPU-verifiable — the bandwidth win is a TPU
property of the format.

The reference has no serving quantization story at all; this is a
TPU-first extra riding the DataParallelTrainer predict seam
(sdk/jax_backend.py): ``DataParallelTrainer(..., serve_int8=True)`` or
``RAFIKI_SERVE_INT8=1`` for any SDK-trainer template. Note the env
switch also applies to trial-time ``evaluate`` — deliberate: trials are
then SELECTED by the accuracy they will actually serve.

RETIRED FROM THE DEFAULTS (r8): the official bench measured
``int8_unloaded_speedup = 0.805`` — a slowdown — on the bench CNN's
matmul shapes (VERDICT r5): those kernels are small enough that the
in-graph dequantize costs more than the weight-stream saving returns.
The numerics stay correct and test-bounded, and the path remains
available for genuinely weight-bandwidth-bound models (large kernels,
batch ~1) — but ``doctor`` WARNs while ``RAFIKI_SERVE_INT8=1`` is set
and no cell measures it on the chip (ROADMAP D3). See
docs/performance.md.
"""

from __future__ import annotations

import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


def serve_int8_enabled() -> bool:
    return os.environ.get("RAFIKI_SERVE_INT8") == "1"


def _is_qleaf(x: Any) -> bool:
    return (isinstance(x, dict) and set(x.keys()) == {"q", "scale"}
            and getattr(x["q"], "dtype", None) == jnp.int8)


#: public name (fused-ensemble stacking walks quantized trees leaf-wise)
is_quantized_leaf = _is_qleaf


def quantize_pytree(params: Any, min_elems: int = 4096) -> Any:
    """Replace large float kernels (ndim >= 2) with
    ``{"q": int8, "scale": f32 per-last-axis-channel}``; biases, norms,
    and small leaves pass through untouched (their bytes are noise and
    their precision matters more). Symmetric round-to-nearest with the
    scale chosen so +-max maps to +-127."""

    def q(leaf):
        a = np.asarray(leaf)
        if (a.ndim < 2 or a.size < min_elems
                or not (np.issubdtype(a.dtype, np.floating)
                        or a.dtype == jnp.bfloat16)):
            return leaf
        orig_dtype = a.dtype
        a = a.astype(np.float32)
        amax = np.max(np.abs(a), axis=tuple(range(a.ndim - 1)),
                      keepdims=True)
        scale = np.maximum(amax / 127.0, 1e-12).astype(np.float32)
        qv = np.clip(np.rint(a / scale), -127, 127).astype(np.int8)
        # the scale carries the SOURCE dtype, so dequant reconstructs
        # exactly the dtype the model computed with (a bf16 kernel must
        # not come back f32 and silently promote the activation matmul)
        return {"q": jnp.asarray(qv),
                "scale": jnp.asarray(scale).astype(orig_dtype)}

    return jax.tree.map(q, params)


def dequantize_pytree(qparams: Any) -> Any:
    """Inverse of :func:`quantize_pytree`; traced inside the jitted
    predict so XLA fuses the multiply into each weight's consumer and the
    int8 copy is what lives in (and streams from) HBM. Reconstructs each
    kernel in its source dtype (carried by the scale)."""

    def dq(leaf):
        if _is_qleaf(leaf):
            dtype = leaf["scale"].dtype
            return leaf["q"].astype(dtype) * leaf["scale"]
        return leaf

    return jax.tree.map(dq, qparams, is_leaf=_is_qleaf)


def quantized_bytes(qparams: Any) -> int:
    """Serving-weight footprint in bytes (the HBM-traffic claim,
    inspectable)."""
    total = 0
    for leaf in jax.tree.leaves(
            qparams, is_leaf=_is_qleaf):
        if _is_qleaf(leaf):
            total += leaf["q"].size + leaf["scale"].size * 4
        elif hasattr(leaf, "nbytes"):
            total += leaf.nbytes
    return total
