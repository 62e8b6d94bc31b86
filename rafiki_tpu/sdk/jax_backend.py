"""JAX/XLA training backend for model templates.

This is the seam the whole rebuild pivots on: where the reference's model
templates each hand-rolled a TF1 session loop on whatever GPU the container
saw (e.g. reference examples/models/image_classification/TfFeedForward.py:55-67),
models here describe *pure functions* — ``init_fn(rng) -> params`` and
``loss_fn(params, batch, rng) -> (loss, aux)`` — and the framework:

- jits one fused train step (forward + backward + optimizer) with donated
  buffers, so weights never leave HBM between steps;
- shards the batch over the mesh's ``data`` axis and replicates params; XLA
  inserts the gradient ``psum`` over ICI (the TPU-native replacement for the
  reference's only collective, ``tf.contrib.nccl.all_sum`` at
  pg_gans.py:1165-1170);
- keeps shapes static (remainder batches are dropped in training and padded +
  masked in eval) so the step compiles once per (model, static-knob) bucket.
"""

from __future__ import annotations

import collections
import logging
import os
import threading
import time
from functools import partial
from typing import Any, Callable, Dict, Hashable, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from rafiki_tpu.parallel.mesh import DATA_AXIS, get_default_mesh, visible_devices
from rafiki_tpu.sdk.log import StopTrialEarly

LossFn = Callable[[Any, Any, jax.Array], Tuple[jax.Array, Dict[str, jax.Array]]]

logger = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# Cross-trial compile reuse (SURVEY.md §7.3's trials/hour lever).
#
# The reference paid a container boot + pip install per trial (reference
# scripts/start_worker.py:6-9); the TPU-native equivalent of that tax is XLA
# recompilation. Two layers kill it:
#
# 1. `cached_trainer`: a process-level cache of trainer objects keyed by
#    (model-declared static signature, this thread's device grant). Trials
#    whose knobs differ only in *dynamic* hyperparameters (lr via
#    `tunable_optimizer`) reuse the same jitted train step — zero retrace.
# 2. `sdk.compile_cache.enable`: JAX's on-disk executable cache, so
#    even fresh executor *processes* (ProcessPlacementManager) skip
#    compilation for programs any previous process already built.

_trainer_cache: "collections.OrderedDict[Hashable, Any]" = collections.OrderedDict()
_trainer_cache_lock = threading.Lock()
_TRAINER_CACHE_CAP = int(os.environ.get("RAFIKI_TRAINER_CACHE_CAP", "8"))
# datasets at or below this size are replicated on-device so fit() can run
# each epoch as a single lax.scan dispatch (see DataParallelTrainer.fit)
_SCAN_EPOCH_MAX_BYTES = int(
    os.environ.get("RAFIKI_SCAN_EPOCH_MAX_BYTES", str(256 << 20)))


def cached_trainer(key: Hashable, build: Callable[[], Any]) -> Any:
    """Return a cached trainer for `key` (scoped to this thread's device
    grant), building it with `build()` on first use.

    The key must cover every knob that changes the *compiled program*:
    architecture knobs, batch/image sizes if they alter shapes the trainer
    bakes in, and the model class identity. Dynamic knobs (lr through
    `tunable_optimizer`) stay out of the key — that is the point. LRU-capped
    (RAFIKI_TRAINER_CACHE_CAP, default 8): evicted trainers just free their
    executables; params live outside the trainer so nothing else is lost.
    """
    grant = tuple(d.id for d in visible_devices())
    full_key = (key, grant)
    with _trainer_cache_lock:
        if full_key in _trainer_cache:
            _trainer_cache.move_to_end(full_key)
            return _trainer_cache[full_key]
    trainer = build()
    with _trainer_cache_lock:
        if full_key not in _trainer_cache:
            _trainer_cache[full_key] = trainer
            while len(_trainer_cache) > _TRAINER_CACHE_CAP:
                _trainer_cache.popitem(last=False)
        _trainer_cache.move_to_end(full_key)
        return _trainer_cache[full_key]


def trainer_cache_clear() -> None:
    with _trainer_cache_lock:
        _trainer_cache.clear()


def tunable_optimizer(make: Callable[..., optax.GradientTransformation],
                      **hyperparams: float) -> optax.GradientTransformation:
    """Wrap an optax factory so its hyperparameters become *dynamic* state
    (optax.inject_hyperparams): ``tunable_optimizer(optax.adamw,
    learning_rate=3e-4)``. The jitted train step is then identical for every
    value — trials differing only in these knobs share one executable; the
    per-trial value is set at ``DataParallelTrainer.init(...,
    hyperparams={...})`` time."""
    return optax.inject_hyperparams(make)(**hyperparams)


def set_opt_hyperparams(opt_state: Any, hyperparams: Dict[str, float]) -> Any:
    """Override injected hyperparameter values in an opt_state produced by a
    `tunable_optimizer` (no-op keys raise — a typo must not silently train
    at the wrong lr)."""
    hp = getattr(opt_state, "hyperparams", None)
    if hp is None:
        raise ValueError(
            "opt_state has no injected hyperparams; build the optimizer "
            "with tunable_optimizer(...) to tune it across cached trials")
    for k, v in hyperparams.items():
        if k not in hp:
            raise KeyError(f"optimizer has no hyperparam {k!r}; has {list(hp)}")
        hp[k] = jnp.asarray(v, dtype=jnp.asarray(hp[k]).dtype)
    return opt_state


def restore_checkpoint_host(path: str, params: Any, opt_state: Any,
                            state: Any = None) -> Dict[str, Any]:
    """Read a fit checkpoint into host pytrees shaped like the given
    targets (the single place the on-disk format is interpreted — both
    DataParallelTrainer and PopulationTrainer restore through here).
    Checkpoints written before the stateful-trainer change carry no
    "state" entry; from_bytes rejects extra target keys, so fall back to a
    matching stateless target (resume must survive a worker upgrade
    mid-trial). try/except rather than pre-parsing: a second full msgpack
    parse would double restore time and host memory."""
    from flax import serialization

    from rafiki_tpu.sdk.artifact import read_artifact

    # verified read: checksummed checkpoints raise the typed
    # ArtifactCorruptError on damage; pre-checksum files pass through
    blob = read_artifact(path)
    target = {"params": params, "opt_state": opt_state,
              "state": state if state is not None else {}, "epoch": 0}
    try:
        return serialization.from_bytes(target, blob)
    except ValueError:
        target = dict(target)
        target.pop("state")
        restored = dict(serialization.from_bytes(target, blob))
        restored["state"] = state if state is not None else {}
        return restored


def shuffled_batches(
    n: int, batch_size: int, rng: np.random.Generator, drop_remainder: bool = True
) -> Iterator[np.ndarray]:
    """Yield shuffled index batches of a fixed size (static shapes for XLA)."""
    perm = rng.permutation(n)
    n_full = n // batch_size
    for i in range(n_full):
        yield perm[i * batch_size : (i + 1) * batch_size]
    if not drop_remainder and n % batch_size:
        yield perm[n_full * batch_size :]


class DataParallelTrainer:
    """Data-parallel trainer over a device mesh.

    Parameters are replicated; batches are sharded on the ``data`` axis.
    Works identically on one chip (mesh of 1) and a v5e-8 slice — only the
    mesh changes, which the placement layer provides.
    """

    def __init__(
        self,
        loss_fn: LossFn,
        optimizer: optax.GradientTransformation,
        predict_fn: Optional[Callable[..., jax.Array]] = None,
        mesh: Optional[Mesh] = None,
        stateful: bool = False,
        serve_int8: Optional[bool] = None,
    ):
        """``stateful=True`` threads a non-trained model state pytree
        (BatchNorm running statistics, EMA copies, ...) through training:

        - ``loss_fn(params, state, batch, rng) -> (loss, (aux, new_state))``
        - ``init_fn(rng) -> (params, state)``; ``init`` returns
          ``(params, opt_state, state)``
        - ``fit(..., state=state)`` returns ``(params, opt_state, state)``
        - ``predict_fn(params, state, x)``; predict/warm take ``state=``

        The state is replicated like params, carried by value through the
        jitted step (donated, so it never leaves HBM), checkpointed next to
        params, and explicitly NOT touched by the optimizer — the trap of
        stuffing it into the params pytree (zero gradients, but weight
        decay would still corrupt it)."""
        self.mesh = mesh or get_default_mesh()
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.predict_fn = predict_fn
        self.stateful = stateful
        self._repl = NamedSharding(self.mesh, P())
        self._data = NamedSharding(self.mesh, P(DATA_AXIS))
        self.n_data = self.mesh.shape[DATA_AXIS]

        # one step body for both modes: `state` is an empty tuple when
        # stateless, so grads/updates/donation logic can't diverge between
        # the two variants
        n_state = 1 if stateful else 0

        def train_step(params, opt_state, state, batch, rng):
            if stateful:
                (loss, (aux, state)), grads = jax.value_and_grad(
                    self.loss_fn, has_aux=True)(params, state, batch, rng)
            else:
                (loss, aux), grads = jax.value_and_grad(
                    self.loss_fn, has_aux=True)(params, batch, rng)
            updates, opt_state = self.optimizer.update(
                grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, state, loss, aux

        self._train_step = jax.jit(
            train_step,
            donate_argnums=(0, 1, 2),
            in_shardings=(self._repl,) * 3 + (self._data, self._repl),
            out_shardings=(self._repl,) * 5,
        )

        # Device-resident epoch scan: the whole epoch as ONE dispatch. The
        # per-step loop pays a host->device put plus a dispatch per batch,
        # and between steps the device waits on the host — which for small
        # AutoML datasets dwarfs the compute. Here the dataset is uploaded
        # once (replicated), the shuffled index matrix ships as a single
        # (n_steps, batch) array, and lax.scan runs the SAME train_step
        # body per row — identical op order and rng schedule to the loop,
        # so the two paths are numerically interchangeable.
        def epoch_scan(params, opt_state, state, data_dev, idx_mat,
                       epoch_key):
            def body(carry, step):
                p, o, s = carry
                i, idx = step
                batch = tuple(
                    jax.lax.with_sharding_constraint(
                        jnp.take(d, idx, axis=0), self._data)
                    for d in data_dev)
                p, o, s, loss, _ = train_step(
                    p, o, s, batch, jax.random.fold_in(epoch_key, i))
                return (p, o, s), loss

            (params, opt_state, state), losses = jax.lax.scan(
                body, (params, opt_state, state),
                (jnp.arange(idx_mat.shape[0]), idx_mat))
            return params, opt_state, state, losses

        self._epoch_scan = jax.jit(
            epoch_scan,
            donate_argnums=(0, 1, 2),
            in_shardings=(self._repl,) * 6,
            out_shardings=(self._repl,) * 4,
        )
        # int8 weight-only serving (sdk/quant.py): quantize once per
        # params identity host-side; the jitted predict dequantizes
        # in-graph so the int8 copy is the HBM-resident one. Explicit
        # arg wins over the env switch.
        from rafiki_tpu.sdk.quant import serve_int8_enabled

        self.serve_int8 = (serve_int8 if serve_int8 is not None
                           else serve_int8_enabled())
        self._qcache: Tuple[Any, Any] = (None, None)  # (params_ref, qparams)
        if predict_fn is not None:
            serving_fn = predict_fn
            if self.serve_int8:
                from rafiki_tpu.sdk.quant import dequantize_pytree

                def serving_fn(qp, *rest, _fn=predict_fn):
                    return _fn(dequantize_pytree(qp), *rest)

            self._predict = jax.jit(
                serving_fn,
                in_shardings=(self._repl,) * (1 + n_state) + (self._data,),
                out_shardings=self._data,
            )

    # -- helpers ----------------------------------------------------------

    def round_batch(self, batch_size: int) -> int:
        """Round a batch size up to a multiple of the data-axis size."""
        r = -(-batch_size // self.n_data)
        return r * self.n_data

    # Smallest predict bucket: padding 1 query to 8 wastes negligible
    # compute, while halving the number of distinct compiled shapes.
    MIN_PREDICT_BUCKET = 8

    def predict_buckets(self, cap: int) -> list:
        """The fixed ladder of compiled predict batch shapes: powers of two
        from MIN_PREDICT_BUCKET up to ``cap`` (each rounded to a multiple of
        the data-axis size) — at most log2(cap) executables ever exist, no
        matter what batch sizes arrive at serving time."""
        buckets = []
        b = self.MIN_PREDICT_BUCKET
        while b < cap:
            buckets.append(self.round_batch(b))
            b *= 2
        buckets.append(self.round_batch(cap))
        # rounding can collapse adjacent powers of two on wide meshes
        return sorted(set(buckets))

    def _bucket_for(self, n: int, cap: int) -> int:
        for b in self.predict_buckets(cap):
            if b >= n:
                return b
        return self.predict_buckets(cap)[-1]

    def device_put_params(self, params: Any) -> Any:
        return jax.device_put(params, self._repl)

    def init(self, init_fn: Callable[[jax.Array], Any], seed: int = 0,
             hyperparams: Optional[Dict[str, float]] = None):
        """Initialize (params, opt_state[, state]), replicated over the
        mesh (state only for stateful trainers, whose ``init_fn`` returns
        ``(params, state)``).

        ``hyperparams`` overrides injected optimizer values (see
        `tunable_optimizer`) — how a cached trainer gets this trial's lr."""
        out = init_fn(jax.random.key(seed))
        state = None
        if self.stateful:
            params, state = out
            state = jax.device_put(state, self._repl)
        else:
            params = out
        params = self.device_put_params(params)
        opt_state = self.optimizer.init(params)
        if hyperparams:
            opt_state = set_opt_hyperparams(opt_state, hyperparams)
        opt_state = jax.device_put(opt_state, self._repl)
        if self.stateful:
            return params, opt_state, state
        return params, opt_state

    # -- training ---------------------------------------------------------

    def fit(
        self,
        params: Any,
        opt_state: Any,
        data: Tuple[np.ndarray, ...],
        epochs: int,
        batch_size: int,
        seed: int = 0,
        log: Optional[Callable[..., None]] = None,
        checkpoint_path: Optional[str] = None,
        checkpoint_every_epochs: int = 1,
        state: Any = None,
        scan_epoch: Optional[bool] = None,
    ):
        """Run the epoch loop over in-memory arrays. Returns
        ``(params, opt_state)``, or ``(params, opt_state, state)`` for
        stateful trainers (pass the initial ``state=`` in).

        ``data`` is a tuple of arrays with equal leading dim; each step gets
        the corresponding tuple slice as ``batch``.

        Mid-trial checkpointing (an upgrade over the reference, whose only
        persistence was the end-of-trial params pickle — a killed trial
        restarted from scratch, reference worker/train.py:122-132): with
        ``checkpoint_path`` set, (params, opt_state, epoch) are written
        atomically every ``checkpoint_every_epochs``, and a fit() that finds
        the file resumes from the saved epoch. The rng schedule is a pure
        function of (seed, epoch), so a resumed run takes exactly the steps
        the uninterrupted run would have.

        ``scan_epoch`` selects the device-resident epoch scan (one dispatch
        per epoch; see ``epoch_scan`` in ``__init__``). Default ``None`` =
        auto: on when the dataset fits the replication budget
        (``RAFIKI_SCAN_EPOCH_MAX_BYTES``, 256 MB; ``RAFIKI_SCAN_EPOCH`` =
        on/off/auto overrides). Both paths produce the same result.
        """
        n = len(data[0])
        # Largest multiple of the data-axis size that fits in the dataset;
        # if the dataset is smaller than the mesh, resample with replacement
        # up to one full device batch so fit() always takes >= 1 step/epoch.
        fit_cap = (n // self.n_data) * self.n_data
        batch_size = min(self.round_batch(batch_size), fit_cap or self.n_data)
        start_epoch = 0
        if checkpoint_path and os.path.exists(checkpoint_path):
            try:
                params, opt_state, state, start_epoch = (
                    self._restore_checkpoint(
                        checkpoint_path, params, opt_state, state))
                logger.info("resuming fit from %s at epoch %d",
                            checkpoint_path, start_epoch)
            except Exception:
                # corrupt/unreadable checkpoint (failed checksum, torn
                # legacy file): warn and train from scratch — losing the
                # saved epochs beats crashing the whole trial over a
                # damaged cache of them
                logger.warning(
                    "checkpoint %s is corrupt or unreadable; restarting "
                    "the trial from scratch", checkpoint_path,
                    exc_info=True)
                start_epoch = 0
        if scan_epoch is None:
            env = os.environ.get("RAFIKI_SCAN_EPOCH", "auto").lower()
            if env in ("0", "off", "false"):
                scan_epoch = False
            elif env in ("1", "on", "true"):
                scan_epoch = True
            else:
                scan_epoch = (sum(int(d.nbytes) for d in data)
                              <= _SCAN_EPOCH_MAX_BYTES)
        data_dev = None  # uploaded lazily: a resume at epoch==epochs skips it
        # Cross-fit device cache: HPO trials of one job call fit() with the
        # SAME host arrays (dataset_utils memoizes loads), and this trainer
        # object persists across trials (cached_trainer) — re-uploading
        # the whole dataset per trial is pure per-trial overhead. Keyed by array identity; the
        # cached entry holds the host arrays too, so ids cannot be reused
        # while the key is alive. One entry (one job, one dataset).
        cache_key = tuple(id(d) for d in data)
        cached = getattr(self, "_fit_data_cache", None)
        if cached is not None and cached[0] == cache_key:
            data_dev = cached[2]
        elif cached is not None:
            # different dataset: drop the stale entry NOW so its device
            # replication frees before the new upload (and doesn't leak if
            # this fit takes the non-scan path)
            self._fit_data_cache = None
        base_key = jax.random.key(seed + 1)
        for epoch in range(start_epoch, epochs):
            t0 = time.time()
            epoch_rng = np.random.default_rng([seed, epoch])
            epoch_key = jax.random.fold_in(base_key, epoch)
            if fit_cap == 0:
                batches: Any = [epoch_rng.choice(n, self.n_data)]
            else:
                batches = shuffled_batches(n, batch_size, epoch_rng)
            if scan_epoch:
                if data_dev is None:
                    data_dev = tuple(
                        jax.device_put(np.asarray(d), self._repl)
                        for d in data)
                    self._fit_data_cache = (cache_key, tuple(data), data_dev)
                idx_mat = jnp.asarray(np.stack(list(batches)), jnp.int32)
                params, opt_state, state, losses = self._epoch_scan(
                    params, opt_state, state, data_dev, idx_mat, epoch_key)
            else:
                losses = []
                for i, idx in enumerate(batches):
                    batch = tuple(
                        jax.device_put(d[idx], self._data) for d in data)
                    step_rng = jax.random.fold_in(epoch_key, i)
                    params, opt_state, state, loss, _ = self._train_step(
                        params, opt_state, state, batch, step_rng)
                    losses.append(loss)
                losses = jnp.stack(losses) if losses else jnp.zeros((0,))
            stop_early = False
            if len(losses) and log is not None:
                try:
                    log(loss=float(jnp.mean(losses)), epoch=float(epoch),
                        epoch_time=time.time() - t0)
                except StopTrialEarly:
                    # scheduler verdict (ASHA): this trial is not
                    # competitive — stop training here and return what it
                    # learned; the caller evaluates and completes normally
                    logger.info("early stop after epoch %d", epoch)
                    stop_early = True
            if checkpoint_path and (
                    (epoch + 1) % max(checkpoint_every_epochs, 1) == 0
                    or epoch + 1 == epochs or stop_early):
                self._save_checkpoint(checkpoint_path, params, opt_state,
                                      epoch + 1, state)
            if stop_early:
                break
        if self.stateful:
            return params, opt_state, state
        return params, opt_state

    @staticmethod
    def _save_checkpoint(path: str, params: Any, opt_state: Any,
                         next_epoch: int, state: Any = None) -> None:
        from flax import serialization

        from rafiki_tpu.sdk.params import _to_host

        # to_bytes state-dict-ifies optax's tuple/NamedTuple states (raw
        # msgpack cannot pack tuples); from_bytes restores into the live
        # structures
        blob = serialization.to_bytes({
            "params": _to_host(params),
            "opt_state": _to_host(opt_state),
            "state": _to_host(state) if state is not None else {},
            "epoch": next_epoch,
        })
        from rafiki_tpu.sdk.artifact import write_artifact

        # atomic (tmp + fsync + rename) AND checksummed: a resumed fit
        # must be able to TELL a bit-rotten checkpoint from a valid one
        # and fall back to a fresh start instead of crashing the trial
        write_artifact(path, blob)

    def _restore_checkpoint(self, path: str, params: Any, opt_state: Any,
                            state: Any = None) -> Tuple[Any, Any, Any, int]:
        """Restore into the shapes of freshly-initialized (params,
        opt_state[, state]) — flax's from-target restore keeps optax's
        NamedTuple state structure intact.

        Restored param shapes are verified against the fit's own target
        BEFORE anything reaches the device: flax takes the blob's array
        shapes at face value, so a checkpoint written under a different
        program — a population-stacked (K, ...) checkpoint left behind by
        a crashed vmapped batch whose lead trial is now re-run scalar, or
        an architecture-knob change — would otherwise restore "cleanly"
        and die later as a cryptic shape error inside the jitted step
        (classified USER, terminally erroring a perfectly good trial). A
        mismatch is typed artifact corruption: fit()'s restore guard logs
        it and starts fresh, the standard corrupt-checkpoint contract."""
        from rafiki_tpu.sdk.artifact import ArtifactCorruptError

        restored = restore_checkpoint_host(path, params, opt_state, state)
        got = [np.shape(x) for x in jax.tree.leaves(restored["params"])]
        want = [np.shape(x) for x in jax.tree.leaves(params)]
        if got != want:
            raise ArtifactCorruptError(
                path,
                f"checkpoint param shapes {got[:4]}{'…' if len(got) > 4 else ''} "
                f"do not match this trial's {want[:4]}"
                f"{'…' if len(want) > 4 else ''} — written under a different "
                f"program (population-stacked, or different architecture "
                f"knobs); treating as corrupt (fresh start)")
        params = self.device_put_params(restored["params"])
        opt_state = jax.device_put(restored["opt_state"], self._repl)
        if state is not None:
            state = jax.device_put(restored["state"], self._repl)
        return params, opt_state, state, int(restored["epoch"])

    # -- inference --------------------------------------------------------

    def _serving_params(self, params: Any) -> Any:
        """The params actually fed to the jitted predict: the int8 copy
        when serve_int8 is on (quantized once per params object — the
        cache holds the source pytree so CPython id reuse can't alias a
        different trial's weights)."""
        if not self.serve_int8:
            return params
        src, qp = self._qcache
        if src is not params:
            from rafiki_tpu.sdk.quant import quantize_pytree

            qp = jax.device_put(quantize_pytree(params), self._repl)
            self._qcache = (params, qp)
        return qp

    def _run_predict(self, params: Any, chunk: np.ndarray,
                     state: Any) -> jax.Array:
        params = self._serving_params(params)
        dev = jax.device_put(chunk, self._data)
        if self.stateful:
            return self._predict(params, state, dev)
        return self._predict(params, dev)

    def predict_batched(
        self, params: Any, x: np.ndarray, batch_size: int = 256,
        state: Any = None,
    ) -> np.ndarray:
        """Run ``predict_fn`` over `x` in power-of-two padded buckets.

        Serving batch sizes vary with load (the continuous batcher drains
        1..cap queries per tick); compiling a shape per distinct size would
        recompile mid-traffic and blow the tail latency. Instead every chunk
        is padded up to the fixed bucket ladder (`predict_buckets`), so the
        set of compiled shapes is small, static, and warmable at deploy.
        """
        assert self.predict_fn is not None, "no predict_fn configured"
        outs = []
        for chunk, pad in self._bucket_chunks(x, batch_size):
            out = np.asarray(self._run_predict(params, chunk, state))
            outs.append(out[: len(out) - pad] if pad else out)
        return np.concatenate(outs) if outs else np.zeros((0,))

    def warm_predict(self, params: Any, example: np.ndarray,
                     batch_size: int = 256, state: Any = None) -> int:
        """Compile every predict bucket up front by running ``predict_fn``
        on copies of ``example`` (one query's worth of input) at each bucket
        size. Called at serving deploy so no real request ever pays a
        compile. Returns the number of buckets warmed."""
        assert self.predict_fn is not None, "no predict_fn configured"
        return self._warm_buckets(
            lambda chunk: self._run_predict(params, chunk, state),
            example, batch_size)

    # -- fused ensemble serving -------------------------------------------

    def _stacked_jit(self):
        """The vmapped predict executable for fused-ensemble serving:
        ``(stacked_params, x) -> (n_models, batch, ...)`` — every co-served
        model answers the batch in ONE device dispatch instead of one
        dispatch per trial (SURVEY §7 "ensembles across trials on one chip
        set"). Runs under the trainer's mesh shardings — params replicated,
        batch over the data axis — so CHIPS_PER_WORKER grants shard the
        fused dispatch exactly like the single-model predict. int8 serving
        composes: each model is quantized individually (see
        ``stack_ensemble_params``) and dequantized in-graph per vmap
        instance."""
        jitted = getattr(self, "_predict_stacked", None)
        if jitted is None:
            assert self.predict_fn is not None, "no predict_fn configured"
            assert not self.stateful, (
                "fused ensemble serving supports stateless predict only")
            serving_fn = self.predict_fn
            if self.serve_int8:
                from rafiki_tpu.sdk.quant import dequantize_pytree

                def serving_fn(qp, x, _fn=self.predict_fn):
                    return _fn(dequantize_pytree(qp), x)

            jitted = self._predict_stacked = jax.jit(
                jax.vmap(serving_fn, in_axes=(0, None)),
                in_shardings=(self._repl, self._data),
                out_shardings=NamedSharding(self.mesh, P(None, DATA_AXIS)),
            )
        return jitted

    def stack_ensemble_params(self, params_list: list) -> Any:
        """Stack N models' param trees along a new leading axis and place
        them on the serving devices — the co-resident ensemble's HBM
        layout. Under int8 serving each model's tree is quantized
        INDIVIDUALLY first (its own per-channel scales, its own
        small-leaf pass-through gates — identical numerics to its solo
        int8 serving) and the q/scale leaves are then stacked."""
        if self.serve_int8:
            from rafiki_tpu.sdk.quant import is_quantized_leaf, quantize_pytree

            qlist = [quantize_pytree(p) for p in params_list]

            def stack_leaf(*xs):
                if is_quantized_leaf(xs[0]):
                    return {
                        "q": np.stack([np.asarray(x["q"]) for x in xs]),
                        "scale": np.stack(
                            [np.asarray(x["scale"]) for x in xs]),
                    }
                return np.stack([np.asarray(x) for x in xs])

            stacked = jax.tree.map(stack_leaf, *qlist,
                                   is_leaf=is_quantized_leaf)
        else:
            stacked = jax.tree.map(
                lambda *xs: np.stack([np.asarray(x) for x in xs]),
                *params_list)
        return jax.device_put(stacked, self._repl)

    def _bucket_chunks(self, x: np.ndarray, batch_size: int):
        """Shared bucket walk for the predict paths: yields
        ``(padded_chunk, pad)`` per bucket on the fixed ladder (the single
        home of the pad-with-repeat rule — the stacked and single-model
        paths must never drift)."""
        n = len(x)
        cap = self.round_batch(max(batch_size, 1))
        i = 0
        while i < n:
            chunk = x[i: i + cap]
            bucket = self._bucket_for(len(chunk), cap)
            pad = bucket - len(chunk)
            if pad:
                chunk = np.concatenate(
                    [chunk, np.repeat(chunk[-1:], pad, axis=0)])
            yield chunk, pad
            i += bucket - pad

    def predict_batched_stacked(
        self, stacked_params: Any, x: np.ndarray, batch_size: int = 256,
    ) -> np.ndarray:
        """``predict_batched`` for a fused ensemble: returns
        ``(n_models, len(x), ...)`` predictions, one vmapped dispatch per
        padded bucket (same bucket ladder/compile-count guarantees)."""
        jitted = self._stacked_jit()
        outs = []
        for chunk, pad in self._bucket_chunks(x, batch_size):
            out = np.asarray(jitted(stacked_params, chunk))
            outs.append(out[:, : out.shape[1] - pad] if pad else out)
        if not outs:
            n_models = np.shape(jax.tree.leaves(stacked_params)[0])[0]
            return np.zeros((n_models, 0))
        return np.concatenate(outs, axis=1)

    def warm_predict_stacked(self, stacked_params: Any, example: np.ndarray,
                             batch_size: int = 256) -> int:
        """``warm_predict`` for the fused-ensemble path."""
        jitted = self._stacked_jit()
        return self._warm_buckets(
            lambda chunk: jitted(stacked_params, chunk), example, batch_size)

    def _warm_buckets(self, run, example: np.ndarray,
                      batch_size: int) -> int:
        """Shared deploy-time bucket warm-up: run ``run(chunk)`` once per
        ladder rung so no real request ever pays an XLA compile."""
        example = np.asarray(example)
        cap = self.round_batch(max(batch_size, 1))
        buckets = self.predict_buckets(cap)
        for b in buckets:
            chunk = np.broadcast_to(example[None], (b,) + example.shape)
            run(np.ascontiguousarray(chunk))
        return len(buckets)


def trainer_ensemble_stack(models: list, example: np.ndarray,
                           to_predictions=None, to_batch=None):
    """Generic ``BaseModel.ensemble_stack`` implementation for SDK-trainer
    templates: fuse ``models`` (each with ``_trainer`` / ``_params``
    attributes, the full co-served group) into one vmapped predict over
    stacked params, or return None when they cannot share a compiled
    predict. ``example`` is one query's worth of input for deploy warm-up;
    ``to_predictions(out_row) -> list`` converts one model's raw output
    batch (default: ``.tolist()`` per row); ``to_batch(queries) ->
    np.ndarray`` converts raw queries into the predict batch (default:
    ``np.asarray(queries, np.float32)`` — text templates pass their
    tokenizer here, see JaxBert). Templates opt in with::

        def ensemble_stack(self, models):
            return trainer_ensemble_stack(
                models, np.zeros(self._example_shape, np.float32))

    Fusion requires every model to hold the SAME trainer instance (the
    ``cached_trainer`` bucket — same template, same architecture knobs)
    and identically-shaped param trees."""
    from rafiki_tpu import config as rconfig

    first = models[0]
    trainer = getattr(first, "_trainer", None)
    if trainer is None or getattr(first, "_params", None) is None:
        return None
    # enforce the contract here, not as a deploy-time assert in the worker:
    # a stateful trainer (batch norm) or one without a predict_fn cannot
    # share a vmapped compiled predict — fall back to sequential serving
    if trainer.stateful or trainer.predict_fn is None:
        return None
    for m in models:
        if getattr(m, "_trainer", None) is not trainer:
            return None
    params_list = [m._params for m in models]
    struct0 = jax.tree.structure(params_list[0])
    shapes0 = [np.shape(x) for x in jax.tree.leaves(params_list[0])]
    for p in params_list[1:]:
        if (jax.tree.structure(p) != struct0
                or [np.shape(x) for x in jax.tree.leaves(p)] != shapes0):
            return None
    stacked = trainer.stack_ensemble_params(params_list)
    # the stacked copy is now the HBM-resident ensemble; keeping every
    # model's own device tree alive too would double the footprint of
    # exactly the worker whose point is co-residency — move the per-model
    # params to host (the sequential fallback never runs once fusion
    # succeeded; plain predict would just re-upload)
    for m in models:
        m._params = jax.tree.map(np.asarray, m._params)
    example = np.asarray(example)
    convert = to_predictions or (lambda out: [row.tolist() for row in out])
    batchify = to_batch or (
        lambda queries: np.asarray(queries, dtype=np.float32))

    class _Fused:
        n_models = len(models)

        @staticmethod
        def predict_all(queries):
            x = batchify(queries)
            out = trainer.predict_batched_stacked(
                stacked, x, batch_size=rconfig.PREDICT_MAX_BATCH_SIZE)
            return [convert(per_model) for per_model in out]

        @staticmethod
        def warm_up():
            trainer.warm_predict_stacked(
                stacked, example,
                batch_size=rconfig.PREDICT_MAX_BATCH_SIZE)

    return _Fused()


def softmax_classifier_loss(apply_fn: Callable[..., jax.Array]) -> LossFn:
    """Standard cross-entropy loss for an ``apply_fn(params, x) -> logits``
    classifier; batch = (x, labels)."""

    def loss_fn(params, batch, rng):
        x, y = batch
        logits = apply_fn(params, x)
        loss = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()
        acc = (jnp.argmax(logits, -1) == y).mean()
        return loss, {"acc": acc}

    return loss_fn


def classification_accuracy(
    trainer: DataParallelTrainer, params: Any, x: np.ndarray, y: np.ndarray
) -> float:
    logits = trainer.predict_batched(params, x)
    return float((np.argmax(logits, -1) == np.asarray(y)).mean())
