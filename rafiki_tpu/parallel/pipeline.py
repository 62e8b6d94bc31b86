"""GPipe-style pipeline parallelism over the ``pipe`` mesh axis.

The layer stack's leading depth axis (models/core.py ``stack_layers``) is
sharded over ``pipe``, so each stage holds depth/n_stages contiguous layers
in HBM — the memory-scaling lever. The batch is split into M microbatches;
activations hop stage-to-stage via ``lax.ppermute`` (point-to-point ICI) on
a schedule of M + n_stages - 1 ticks, and every tick every stage computes —
bubble fraction (n_stages-1)/(M+n_stages-1), the GPipe number.

Differentiable end-to-end (scan + ppermute), so one ``jax.grad`` over the
pipelined forward gives pipeline-parallel training without a hand-written
backward schedule.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from rafiki_tpu.parallel.mesh import DATA_AXIS, PIPELINE_AXIS


def _make_stage_apply(params_local: Any, block_fn):
    def apply_stage(x):
        def body(h, layer):
            return block_fn(layer, h), None
        h, _ = jax.lax.scan(body, x, params_local)
        return h
    return apply_stage


def _stage_local_streamed(params_local: Any, x_local: jax.Array, *, block_fn,
                          axis_name: str, n_microbatches: int) -> jax.Array:
    """Per-stage body with the input microbatches SHARDED over stages.

    x_local: (M'/n, mb, ...) — stage s starts holding queue slots
    [s*M'/n, (s+1)*M'/n), where M' is the microbatch count padded up to a
    multiple of the stage count (gpipe_apply pads; ``n_microbatches`` is
    the REAL count M and alone drives the tick schedule). The shards form
    one distributed queue in stage-major order; every tick it rotates one
    slot toward stage 0 (a backward ``ppermute`` of each stage's head), so
    stage 0's local head is always the next microbatch to feed. Input HBM
    per stage is O(B/n) instead of a replicated feed's O(B) — activation
    memory scales with pipeline depth like the weights do.

    The real microbatches occupy the first M queue slots, so ticks
    0..M-1 feed them in order; ticks past M feed padded/wrapped (dead)
    entries into stage 0, whose outputs can never reach the last stage
    before the M + n - 1 tick schedule ends, so they are never observed.
    """
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    m = n_microbatches

    apply_stage = _make_stage_apply(params_local, block_fn)
    fwd_perm = [(r, (r + 1) % n) for r in range(n)]
    bwd_perm = [(r, (r - 1) % n) for r in range(n)]
    mb_shape = x_local.shape[1:]

    def tick(carry, _t):
        buf, queue = carry
        inp = jnp.where(my == 0, queue[0], buf)
        out = apply_stage(inp)
        nxt = jax.lax.ppermute(out, axis_name, fwd_perm)
        # rotate the distributed queue: my head goes to the previous
        # stage's tail; the next stage's head becomes my tail
        incoming = jax.lax.ppermute(queue[0], axis_name, bwd_perm)
        queue = jnp.concatenate([queue[1:], incoming[None]], axis=0)
        return (nxt, queue), out

    t_total = m + n - 1
    (_, _), outs = jax.lax.scan(
        tick, (jnp.zeros(mb_shape, x_local.dtype), x_local),
        jnp.arange(t_total))
    y = outs[n - 1:]                      # (M, mb, ...)
    y = jnp.where(my == n - 1, y, 0.0)
    return jax.lax.psum(y, axis_name)


def gpipe_apply(block_fn: Callable[[Any, jax.Array], jax.Array],
                stacked_params: Any, x: jax.Array, mesh: Mesh,
                n_microbatches: int,
                pipe_axis: str = PIPELINE_AXIS,
                data_axis: str = DATA_AXIS) -> jax.Array:
    """Run ``block_fn`` over the pipe-sharded layer stack with microbatched
    pipelining. ``x``: (B, ...) with B divisible by n_microbatches; layer
    stack depth divisible by the pipe axis size. If the mesh also has a
    ``data`` axis, the microbatch dim stays data-sharded (DP x PP compose:
    each data shard runs its own pipeline over the same stage weights)."""
    n_stages = mesh.shape[pipe_axis]
    b = x.shape[0]
    assert b % n_microbatches == 0, "batch must divide into microbatches"
    x_mbs = x.reshape(n_microbatches, b // n_microbatches, *x.shape[1:])

    # pad the queue (NOT the schedule) up to a multiple of the stage count
    # so the input microbatches always shard over stages — the padded
    # entries sit behind the real ones and are only ever fed on dead
    # ticks, so no extra compute reaches the output (see
    # _stage_local_streamed). This keeps input HBM at O(B/n) per stage
    # for every M, where a replicated-input fallback would cost O(B).
    pad = (-n_microbatches) % n_stages
    if pad:
        x_mbs = jnp.concatenate(
            [x_mbs, jnp.zeros((pad, *x_mbs.shape[1:]), x_mbs.dtype)], axis=0)

    # keep the microbatch dim data-sharded only when it divides
    dp = data_axis if data_axis in mesh.axis_names else None
    if dp is not None and (b // n_microbatches) % mesh.shape[dp] != 0:
        dp = None
    param_specs = jax.tree.map(lambda _: P(pipe_axis), stacked_params)
    fn = jax.shard_map(
        partial(_stage_local_streamed, block_fn=block_fn,
                axis_name=pipe_axis, n_microbatches=n_microbatches),
        mesh=mesh,
        in_specs=(param_specs, P(pipe_axis, dp)),
        out_specs=P(None, dp),
        check_vma=False,
    )
    y = fn(stacked_params, x_mbs)
    return y.reshape(b, *y.shape[2:])
