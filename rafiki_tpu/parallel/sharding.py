"""GSPMD training: pjit a whole train step over an explicit mesh.

Where the DataParallelTrainer (sdk/jax_backend.py) replicates params and
shards only the batch, this layer takes a *pytree of PartitionSpecs* from the
model (e.g. models/vit.py ``partition_specs``) and lets XLA place every
matmul and insert every collective (psum on row-parallel matmuls, all-gather
on seq-sharded attention) over ICI — the scaling-book recipe: pick a mesh,
annotate shardings, let XLA do the rest.

Spec trees may mention axes the current mesh doesn't have (``model``,
``seq``, ``pipe``, ``expert``); ``filter_pspec`` drops unknown axes so the
same model code runs on a pure-DP mesh, a dp×tp×sp mesh, or a single chip
without edits.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

LossFn = Callable[[Any, Any, jax.Array], Tuple[jax.Array, Dict[str, jax.Array]]]


def filter_pspec(spec: P, mesh: Mesh) -> P:
    """Drop mesh-axis names the mesh doesn't define (so ``model``-sharded
    specs degrade to replicated on a pure-DP mesh, etc.)."""
    names = set(mesh.axis_names)

    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in names)
            return kept if kept else None
        return entry if entry in names else None

    return P(*(keep(e) for e in spec))


def named_shardings(mesh: Mesh, specs: Any) -> Any:
    """Pytree of PartitionSpec -> pytree of NamedSharding (axis-filtered)."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, filter_pspec(s, mesh)),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


# -- activation-sharding hook ------------------------------------------------
# Models call ``shard_activations(x, ("data", "seq", None))`` at block
# boundaries; it is a no-op unless a trainer has installed its mesh here (so
# model code stays mesh-free). Thread-local because trial executors run as
# threads with different meshes (parallel/mesh.py device grants).

_act = threading.local()


@contextmanager
def activation_mesh(mesh: Optional[Mesh]):
    prev = getattr(_act, "mesh", None)
    _act.mesh = mesh
    try:
        yield
    finally:
        _act.mesh = prev


def shard_activations(x: jax.Array, axes: Sequence[Any]) -> jax.Array:
    mesh = getattr(_act, "mesh", None)
    if mesh is None:
        return x
    spec = filter_pspec(P(*axes), mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def current_mesh() -> Optional[Mesh]:
    """The mesh installed by the innermost ``activation_mesh`` (None outside
    a trainer). Models use this to route to mesh-aware paths — ring
    attention over the ``seq`` axis, GPipe over ``pipe`` — without the mesh
    appearing in their signatures.

    Trace-time contract: this is read during jit TRACING, so the routing it
    selects (and the mesh any shard_map binds) is baked into the compiled
    function. A jitted function must therefore be traced and executed under
    the same activation_mesh — keep one jitted closure per mesh, as
    GspmdTrainer does (its ``step``/``predict`` always wrap the per-instance
    jit in ``activation_mesh(self.mesh)``). Don't share one ``jax.jit``
    across different mesh contexts: the first trace's routing wins silently.
    """
    return getattr(_act, "mesh", None)


def mesh_axis_size(axis: str) -> int:
    """Size of ``axis`` on the current mesh (1 if absent / no mesh)."""
    mesh = current_mesh()
    if mesh is None or axis not in mesh.axis_names:
        return 1
    return mesh.shape[axis]


class GspmdTrainer:
    """pjit-style trainer: params sharded per the model's spec tree, batch
    sharded per ``batch_specs``, one fused donated train step.

    Optimizer state inherits its sharding from params via XLA propagation
    (the init is jitted with the param shardings as inputs), so optax states
    of any structure work without spec plumbing.
    """

    def __init__(
        self,
        loss_fn: LossFn,
        optimizer: optax.GradientTransformation,
        param_specs: Any,
        batch_specs: Any,
        mesh: Mesh,
        predict_fn: Optional[Callable[[Any, Any], jax.Array]] = None,
        predict_in_specs: Any = None,
        predict_out_specs: Any = None,
    ):
        self.mesh = mesh
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.param_shardings = named_shardings(mesh, param_specs)
        self.batch_shardings = named_shardings(mesh, batch_specs)
        self._repl = NamedSharding(mesh, P())

        def train_step(params, opt_state, batch, rng):
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch, rng
            )
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            # pin param output shardings so they never drift across steps
            params = jax.lax.with_sharding_constraint(
                params, self.param_shardings
            )
            return params, opt_state, loss, aux

        # params/opt_state shardings are taken from the arguments (committed
        # at init time); batch/rng pinned explicitly.
        self._train_step = jax.jit(train_step, donate_argnums=(0, 1))
        self.predict_fn = predict_fn
        if predict_fn is not None:
            # default: the predict input shards like the first train-batch
            # element (the common (x, y) -> x case)
            if predict_in_specs is None:
                leaves = jax.tree.leaves(
                    batch_specs, is_leaf=lambda s: isinstance(s, P))
                predict_in_specs = leaves[0] if leaves else P()
            self._predict_shardings = named_shardings(mesh, predict_in_specs)
            out_s = (
                named_shardings(mesh, predict_out_specs)
                if predict_out_specs is not None
                else None
            )
            self._predict = jax.jit(predict_fn, out_shardings=out_s)

    # -- lifecycle --------------------------------------------------------

    def init(self, init_fn: Callable[[jax.Array], Any], seed: int = 0
             ) -> Tuple[Any, Any]:
        """Shard-init params and optimizer state directly on the mesh (no
        host-side full materialization beyond the first trace)."""
        rng = jax.random.key(seed)
        with activation_mesh(self.mesh):
            params = jax.jit(
                init_fn, out_shardings=self.param_shardings)(rng)
            opt_state = jax.jit(self.optimizer.init)(params)
        return params, opt_state

    def step(self, params, opt_state, batch, rng):
        batch = jax.device_put(batch, self.batch_shardings)
        with activation_mesh(self.mesh):
            return self._train_step(params, opt_state, batch, rng)

    def predict(self, params, batch):
        assert self.predict_fn is not None
        batch = jax.device_put(batch, self._predict_shardings)
        with activation_mesh(self.mesh):
            return self._predict(params, batch)


def make_train_mesh(
    n_devices: Optional[int] = None,
    dp: Optional[int] = None,
    tp: int = 1,
    sp: int = 1,
    pp: int = 1,
    ep: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a (pipe, data, expert, seq, model) mesh.

    Axis order puts ``model`` innermost — TP traffic is the most
    latency-sensitive, so it rides nearest-neighbour ICI; ``pipe`` outermost
    (stage handoffs are point-to-point and tolerate the longest hops);
    ``data`` next (bandwidth-heavy psums amortize well). Unspecified dp
    absorbs the remaining devices.
    """
    from rafiki_tpu.parallel.mesh import visible_devices

    devs = list(devices if devices is not None else visible_devices())
    if n_devices is not None:
        devs = devs[:n_devices]
    n = len(devs)
    fixed = tp * sp * pp * ep
    if dp is None:
        if n % fixed:
            raise ValueError(f"{n} devices not divisible by tp*sp*pp*ep={fixed}")
        dp = n // fixed
    if dp * fixed != n:
        raise ValueError(f"dp*tp*sp*pp*ep={dp * fixed} != {n} devices")
    arr = np.array(devs).reshape(pp, dp, ep, sp, tp)
    return Mesh(arr, ("pipe", "data", "expert", "seq", "model"))
