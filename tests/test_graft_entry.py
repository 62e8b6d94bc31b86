"""The driver contract: dryrun_multichip must validate every parallelism
mode on a virtual mesh, and the ring/gpipe modes it exercises must be
numerically equivalent to the plain paths (same params, same logits).

Reference analogue: none — the reference has no multi-device simulation
layer at all (SURVEY.md §4); this is the TPU build's pre-hardware gate.
"""

import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from rafiki_tpu.models import lm
from rafiki_tpu.parallel.sharding import activation_mesh, make_train_mesh


def test_lm_ring_mode_matches_dense():
    """seq_parallel='ring' routes through parallel/ring.py and must produce
    the same logits as plain attention for identical params."""
    devs = jax.devices()[:4]
    mesh = make_train_mesh(dp=2, sp=2, devices=devs)
    cfg_dense = lm.tiny(depth=2, max_len=32)
    cfg_ring = lm.tiny(depth=2, max_len=32, seq_parallel="ring")
    params = lm.init(jax.random.key(0), cfg_dense)
    ids = np.asarray(
        jax.random.randint(jax.random.key(1), (2, 32), 0, 256), np.int32)

    dense, _ = jax.jit(lambda p, i: lm.apply(p, i, cfg_dense))(params, ids)
    with activation_mesh(mesh):
        ring, _ = jax.jit(lambda p, i: lm.apply(p, i, cfg_ring))(params, ids)
    # activations flow in bf16; ring accumulation order differs from the
    # dense matmul, so agreement is to bf16 resolution, not f32
    np.testing.assert_allclose(np.asarray(dense), np.asarray(ring),
                               rtol=1e-2, atol=1e-2)


def test_lm_gpipe_mode_matches_scan():
    """pipeline='gpipe' routes through parallel/pipeline.py microbatch
    pipelining and must match the lax.scan depth stack exactly."""
    devs = jax.devices()[:4]
    mesh = make_train_mesh(dp=2, pp=2, devices=devs)
    cfg_scan = lm.tiny(depth=4, max_len=16)
    cfg_pipe = lm.tiny(depth=4, max_len=16, pipeline="gpipe",
                       n_microbatches=2)
    params = lm.init(jax.random.key(0), cfg_scan)
    ids = np.asarray(
        jax.random.randint(jax.random.key(1), (4, 16), 0, 256), np.int32)

    scan, _ = jax.jit(lambda p, i: lm.apply(p, i, cfg_scan))(params, ids)
    with activation_mesh(mesh):
        pipe, _ = jax.jit(lambda p, i: lm.apply(p, i, cfg_pipe))(params, ids)
    np.testing.assert_allclose(np.asarray(scan), np.asarray(pipe),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_dryrun_multichip_in_process():
    """The full driver dryrun on the test env's 8 virtual devices."""
    import __graft_entry__

    __graft_entry__.dryrun_multichip(8)
