"""The model-template contract — what users implement and upload.

Capability parity with the reference's BaseModel (reference
rafiki/model/model.py:20-127): ``get_knob_config`` (static), ``train``,
``evaluate`` -> float score, ``predict`` -> JSON-able list, parameter
dump/load, ``destroy``; plus ``load_model_class`` (deserialize an uploaded
``.py``, reference model.py:221-242) and the local contract harness
``test_model_class`` (reference model.py:129-219).

Differences by design:
- parameters are msgpack'd pytrees, not pickles (see sdk/params.py);
- declared dependencies are *validated as importable*, not pip-installed per
  worker boot (the reference ran ``pip install`` in every container,
  reference scripts/start_worker.py:6-9 — dead time the TPU build eliminates);
- models get a device mesh from the placement layer (chip affinity) instead
  of CUDA_VISIBLE_DEVICES.
"""

from __future__ import annotations

import abc
import importlib.util
import inspect
import json
import os
import sys
import tempfile
import traceback
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from rafiki_tpu.sdk.knob import (
    BaseKnob,
    KnobConfig,
    serialize_knob_config,
    validate_knobs,
)
from rafiki_tpu.sdk.log import ModelLogger, logger as _module_logger


class InvalidModelClassError(Exception):
    pass


class PopulationSpec:
    """Declares that a template can train a POPULATION of knob configs as
    one vmapped XLA program (the trials/hour/chip lever — SURVEY §7.3,
    ROADMAP item 3). Set as a class attribute::

        class MyModel(BaseModel):
            population_spec = PopulationSpec(dynamic_knobs=("learning_rate",))

    ``dynamic_knobs`` names the knobs that may DIFFER across members of
    one vmapped program — pure hyperparameters that ride the optimizer
    state (lr/momentum/weight-decay through ``tunable_optimizer``).
    Every other knob is treated as program-shaping (architecture, batch
    size, epochs): the worker's shape-bucketing partitioner
    (worker/vmap_partition.py) only stacks proposals whose remaining
    knobs are identical, so members of one program always share one
    compiled step.

    ``max_members`` caps how many members the worker stacks into one
    program — the per-chip memory heuristic (stacked params + opt state
    scale linearly with K).

    A template advertising a spec must also implement the three
    population methods on :class:`BaseModel` (``train_population``,
    ``evaluate_population``, ``dump_member_parameters``);
    :func:`population_capability` refuses specs whose methods are still
    the base stubs, so a half-wired template falls back to scalar trials
    instead of crashing the worker."""

    def __init__(self, dynamic_knobs, max_members: int = 8):
        self.dynamic_knobs = tuple(dynamic_knobs)
        if not self.dynamic_knobs:
            raise ValueError(
                "PopulationSpec needs at least one dynamic knob name")
        self.max_members = max(int(max_members), 1)

    def __repr__(self) -> str:
        return (f"PopulationSpec(dynamic_knobs={self.dynamic_knobs!r}, "
                f"max_members={self.max_members})")


class GenerationSpec:
    """Declares that a template can serve the ``TEXT_GENERATION`` task:
    KV-cached autoregressive decode with token-level continuous batching
    (worker/generation.py). Set as a class attribute::

        class MyLM(BaseModel):
            generation_spec = GenerationSpec(eos_token_id=0,
                                             max_context=128)

    ``eos_token_id`` ends a sequence the step it is emitted (None = run to
    ``max_tokens``); ``max_context`` is the KV-cache ring length per slot —
    prompt plus generated tokens must fit, and a sequence reaching it is
    finished with reason ``context``.

    A template advertising a spec must also implement the three decode
    methods on :class:`BaseModel` (``init_kv_cache``, ``prefill``,
    ``decode_step``); :func:`generation_capability` refuses specs whose
    methods are still the base stubs, so a half-wired template is a typed
    deploy error instead of a mid-serving crash.

    ``recurrent_state=True`` declares that a sequence also holds a fixed
    state that every token it has seen went into (a state-space or linear
    layer's), kept by SLOT beside the keys and values. The worker then tells
    the paged methods which slot they serve (``init_paged_kv_cache(...,
    max_slots)``, ``paged_prefill(..., start, slot)``); the model starts the
    slot's state from zero at ``start == 0`` and continues it at
    ``start > 0``, and leaves the state of a decode row whose table is all
    sentinel as it was. Such a state cannot be shared, rewound or rolled
    back, so for this model the worker serves no prefix-cache hit (each
    counts as a miss), resumes a preempted stream from position 0, and
    refuses at deploy a template that also wires sampling or speculative
    verify (a sampled stream's first round replays the prompt's last token;
    a rejected draft would have to leave the state)."""

    def __init__(self, eos_token_id: Optional[int] = None,
                 max_context: int = 128, recurrent_state: bool = False):
        self.eos_token_id = (None if eos_token_id is None
                             else int(eos_token_id))
        self.max_context = max(int(max_context), 2)
        self.recurrent_state = bool(recurrent_state)

    def __repr__(self) -> str:
        return (f"GenerationSpec(eos_token_id={self.eos_token_id!r}, "
                f"max_context={self.max_context}"
                + (", recurrent_state=True" if self.recurrent_state else "")
                + ")")


class BaseModel(abc.ABC):
    """Abstract contract every model template implements.

    Subclasses are instantiated once per trial as ``Model(**knobs)`` with a
    concrete knob assignment proposed by the advisor.
    """

    #: declared dependencies: {package_name: version_spec_or_None}
    dependencies: Dict[str, Optional[str]] = {}

    def __init__(self, **knobs: Any):
        self._knobs = knobs
        self.logger: ModelLogger = _module_logger
        #: set by the train worker before ``train()``: a per-trial file path
        #: templates MAY hand to ``DataParallelTrainer.fit(checkpoint_path=
        #: ...)`` for mid-trial checkpointing — a crashed-and-restarted trial
        #: then resumes from its last epoch instead of from scratch (the
        #: reference always restarted from scratch, reference
        #: worker/train.py:122-132). None when run outside a worker.
        self.checkpoint_path: Optional[str] = None

    @staticmethod
    @abc.abstractmethod
    def get_knob_config() -> KnobConfig:
        """The tunable hyperparameter space for this template."""

    @abc.abstractmethod
    def train(self, dataset_uri: str) -> None:
        """Train on the dataset at `dataset_uri`."""

    @abc.abstractmethod
    def evaluate(self, dataset_uri: str) -> float:
        """Return a scalar score (higher is better) on the dataset."""

    @abc.abstractmethod
    def predict(self, queries: List[Any]) -> List[Any]:
        """Return one JSON-able prediction per query."""

    @abc.abstractmethod
    def dump_parameters(self) -> Any:
        """Return a serializable pytree of trained parameters."""

    @abc.abstractmethod
    def load_parameters(self, params: Any) -> None:
        """Restore trained parameters produced by ``dump_parameters``."""

    def warm_up(self) -> None:
        """Optional serving warm-up, called once by the inference worker
        after ``load_parameters`` and before the service reports ready.

        Implementations should run ``predict`` on representative synthetic
        queries at the batch sizes serving will use (e.g.
        ``DataParallelTrainer.warm_predict``) so every compiled shape exists
        before real traffic arrives — no request ever pays an XLA compile.
        Default: no-op (non-JAX templates have nothing to warm)."""

    def destroy(self) -> None:
        """Release resources (default: no-op)."""

    # -- vectorized trial execution (opt-in via ``population_spec``) -------

    #: set to a :class:`PopulationSpec` to advertise that this template can
    #: train a population of knob configs as ONE vmapped program; the train
    #: worker then drains K advisor proposals per round and runs each
    #: shape-compatible bucket through ``train_population`` instead of one
    #: scalar trial per proposal (worker/train.py).
    population_spec: Optional[PopulationSpec] = None

    def train_population(self, dataset_uri: str,
                         member_knobs: List[Dict[str, Any]]) -> None:
        """Train every member of ``member_knobs`` simultaneously (one
        vmapped program — see sdk/population.PopulationTrainer). The
        instance was constructed with ``member_knobs[0]``; members differ
        only in the spec's ``dynamic_knobs``. ``self.checkpoint_path``
        checkpoints the STACKED pytrees, giving the whole batch the same
        mid-trial resume guarantee as scalar trials."""
        raise NotImplementedError

    def evaluate_population(self, dataset_uri: str) -> List[float]:
        """One score per member, in ``member_knobs`` order. A member whose
        score comes back NaN/inf is failed INDIVIDUALLY by the worker
        (typed INVALID_SCORE + infeasible feedback for that member only),
        never the batch."""
        raise NotImplementedError

    def dump_member_parameters(self, member: int) -> Any:
        """Member ``member``'s parameters in the SAME format
        ``dump_parameters`` produces — each member becomes its own trial
        row with its own params artifact, so serving deploys winners
        exactly like scalar trials."""
        raise NotImplementedError

    # -- generative serving (opt-in via ``generation_spec``) ----------------

    #: set to a :class:`GenerationSpec` to advertise that this template can
    #: serve TEXT_GENERATION: the generation worker then drives the three
    #: decode methods below in a continuous-batching slot loop
    #: (worker/generation.py) instead of the one-request/one-answer
    #: ``predict`` path.
    generation_spec: Optional["GenerationSpec"] = None

    def init_kv_cache(self, max_slots: int) -> Any:
        """Preallocate an opaque decode cache for ``max_slots`` co-resident
        sequences (fixed shapes: one jitted step program serves the cache's
        whole lifetime). Called once by the generation worker after
        ``load_parameters``."""
        raise NotImplementedError

    def prefill(self, cache: Any, slot: int,
                prompt_ids: List[int]) -> Tuple[int, Any]:
        """Ingest a prompt into ``slot`` of ``cache`` and return
        ``(first_generated_token_id, cache)``. Caches are values: return
        the updated cache (JAX pytrees are immutable)."""
        raise NotImplementedError

    def decode_step(self, cache: Any, ids: Any, positions: Any
                    ) -> Tuple[Any, Any]:
        """One token for EVERY slot: ``ids``/``positions`` are int arrays of
        length ``max_slots`` — the last emitted token per slot and the cache
        index it lands at (idle slots carry zeros; their outputs are
        ignored). Returns ``(next_token_ids, cache)``."""
        raise NotImplementedError

    # -- paged decode memory (opt-in refinement of the generation contract)

    def init_paged_kv_cache(self, pool_blocks: int,
                            block_tokens: int) -> Any:
        """Preallocate a BLOCK-POOL decode cache: ``pool_blocks`` pages of
        ``block_tokens`` K/V rows each, instead of one contiguous ring per
        slot. Templates that also override the three ``paged_*`` methods
        below serve under the paged allocator (worker/kv_paging.py) —
        co-resident streams are then bound by *used* tokens, not
        ``slots x max_context`` — and gain shared-prefix caching and
        chunked prefill for free. Templates without them keep the ring
        path unchanged. A template whose spec declares ``recurrent_state``
        is called with a third argument, ``max_slots``, and keeps its
        per-slot state in the same cache."""
        raise NotImplementedError

    def paged_prefill(self, cache: Any, block_table: Any,
                      prompt_ids: List[int], start: int
                      ) -> Tuple[int, Any]:
        """Ingest prompt tokens at logical positions ``start ..
        start + len(prompt_ids) - 1`` of the slot whose physical pages
        are ``block_table`` (int32, fixed width, sentinel = pool size for
        unallocated entries). Returns ``(next_token_id, cache)`` — the
        token is only meaningful when this call covered the prompt's last
        position (chunked prefill ignores intermediate returns). A
        template whose spec declares ``recurrent_state`` is called with a
        fifth argument, ``slot``: the chunk continues that slot's state,
        from zero where ``start == 0``."""
        raise NotImplementedError

    def paged_decode_step(self, cache: Any, ids: Any, positions: Any,
                          block_tables: Any) -> Tuple[Any, Any]:
        """One token for EVERY slot against the block pool:
        ``block_tables`` is (max_slots, W) int32 (idle slots carry
        all-sentinel rows). W varies between calls: the worker hands a
        round the narrowest of a short ladder of widths (128 tokens'
        blocks, doubling, up to the context's) that covers its longest
        live sequence, and calls each width once, all rows idle, before
        it admits a request. A ``jax.jit`` keyed by shape needs nothing
        for that (one program a width); a template that lowers ahead of
        time lowers one program a width. May return a third value, a
        dict of small arrays the decode program counted
        (``expert_tokens``, ``experts_hit``, ``expert_layers``): the
        worker fetches it with the tokens and adds it to the
        ``rafiki_gen_expert*`` counters."""
        raise NotImplementedError

    def recurrent_state_bytes(self, cache: Any) -> int:
        """Bytes of the per-slot recurrent state inside ``cache`` (the
        ``rafiki_gen_state_bytes`` gauge); 0 for a model that has none."""
        return 0

    def kv_copy_blocks(self, cache: Any, src: Any, dst: Any) -> Any:
        """Copy whole pool pages ``src[i] -> dst[i]`` — the allocator's
        copy-on-write primitive (models/lm.py ``copy_kv_blocks``)."""
        raise NotImplementedError

    # -- sampling + speculative decoding (opt-in refinements) ---------------

    def decode_step_sampled(self, cache: Any, ids: Any, positions: Any,
                            sampling: Any) -> Tuple[Any, Any, Any]:
        """``decode_step`` with an in-graph temperature/top-k/top-p draw.

        ``sampling`` is a dict of per-slot arrays — ``seed`` (uint32),
        ``temperature`` (f32), ``top_k`` (int32, 0 = off), ``top_p``
        (f32, 1.0 = off) — plus a scalar ``role`` (see models/lm.py
        ``ROLE_*``). Every draw MUST be keyed
        ``fold_in(fold_in(PRNGKey(seed), token_position), role)`` so
        sampled streams resume exactly after preemption, and
        temperature <= 0 MUST reproduce the greedy argmax bit-identically.
        Returns ``(token_ids, probs, cache)`` where ``probs`` is the FULL
        modified distribution per slot — a draft model's q, the
        denominator of the speculative accept test."""
        raise NotImplementedError

    def paged_decode_step_sampled(self, cache: Any, ids: Any,
                                  positions: Any, block_tables: Any,
                                  sampling: Any) -> Tuple[Any, Any, Any]:
        """``paged_decode_step`` with the same in-graph sampled draw and
        key discipline as ``decode_step_sampled``; ``block_tables`` varies
        in width as it does there (no width is called ahead of time)."""
        raise NotImplementedError

    def paged_verify_step(self, cache: Any, ids: Any, positions: Any,
                          block_tables: Any, draft_probs: Any,
                          sampling: Any) -> Tuple[Any, Any, Any]:
        """Verify k drafted tokens per slot in ONE fixed-shape forward
        (models/lm.py ``paged_verify_step``). ``ids`` (S, k+1) carries
        each slot's last committed token then the draft's k proposals,
        ``positions`` (S, k+1) their write positions, ``draft_probs``
        (S, k, V) the draft's modified distributions. Returns
        ``(accept_len, tokens, cache)``: per-slot accepted-prefix lengths
        (data, not shape — mixed acceptance never retraces) and the
        committed tokens left-packed per row (accept_len + 1 of them:
        accepted prefix plus the rejection-resample or bonus token)."""
        raise NotImplementedError

    def ensemble_stack(self, models: List["BaseModel"]) -> Optional[Any]:
        """Optional fused-ensemble serving hook (budget ``ENSEMBLE_FUSED``).

        ``models`` is the full co-served group, ``self`` included. Return an
        object with ``predict_all(queries) -> [n_models][n_queries]`` (and
        optionally ``warm_up()``) that answers for EVERY model in one device
        dispatch — for SDK-trainer templates that is
        ``DataParallelTrainer.predict_batched_stacked`` over
        ``stack_ensemble_params`` (see JaxCnn.ensemble_stack). Return None
        when the group cannot share a compiled predict (different
        architecture knobs, different param shapes, non-JAX template); the
        fused worker then serves the group sequentially in-process.
        Default: None."""
        return None


def population_capability(clazz: type) -> Optional[PopulationSpec]:
    """The template's :class:`PopulationSpec` iff it is fully wired:
    a spec instance AND all three population methods overridden. Anything
    less returns None — the worker then runs scalar trials (automatic
    fallback; the doctor's "vectorized trials" check surfaces the
    silent-fallback case when population mode was explicitly asked for)."""
    spec = getattr(clazz, "population_spec", None)
    if spec is None:
        return None
    import logging

    if not isinstance(spec, PopulationSpec):
        logging.getLogger(__name__).warning(
            "%s.population_spec is not a PopulationSpec (%s); ignoring — "
            "trials run scalar", clazz.__name__, type(spec).__name__)
        return None
    for name in ("train_population", "evaluate_population",
                 "dump_member_parameters"):
        if getattr(clazz, name, None) is getattr(BaseModel, name):
            logging.getLogger(__name__).warning(
                "%s declares population_spec but does not override %s(); "
                "ignoring — trials run scalar", clazz.__name__, name)
            return None
    return spec


#: the three decode methods a generation-capable template must override
GENERATION_METHODS = ("init_kv_cache", "prefill", "decode_step")


def generation_capability(clazz: type) -> Optional[GenerationSpec]:
    """The template's :class:`GenerationSpec` iff it is fully wired: a
    spec instance AND all three decode methods overridden. Anything less
    returns None — unlike the population fallback there is no scalar path
    to degrade to, so callers (upload validation, the generation worker)
    turn None into a typed error rather than a silent downgrade."""
    spec = getattr(clazz, "generation_spec", None)
    if spec is None:
        return None
    import logging

    if not isinstance(spec, GenerationSpec):
        logging.getLogger(__name__).warning(
            "%s.generation_spec is not a GenerationSpec (%s); ignoring",
            clazz.__name__, type(spec).__name__)
        return None
    for name in GENERATION_METHODS:
        if getattr(clazz, name, None) is getattr(BaseModel, name):
            logging.getLogger(__name__).warning(
                "%s declares generation_spec but does not override %s(); "
                "template is NOT generation-capable", clazz.__name__, name)
            return None
    return spec


#: the additional methods a template must override to serve under the
#: paged KV allocator (block pool + prefix cache + chunked prefill)
GENERATION_PAGED_METHODS = ("init_paged_kv_cache", "paged_prefill",
                            "paged_decode_step", "kv_copy_blocks")


def paged_generation_capability(clazz: type) -> Optional[GenerationSpec]:
    """The template's :class:`GenerationSpec` iff it is paged-capable:
    the full base generation contract PLUS all four paged methods
    overridden. None degrades the worker to the contiguous-ring path —
    a safe fallback (unlike the base contract, where None is a typed
    deploy error), surfaced by the doctor's generative-serving check."""
    spec = generation_capability(clazz)
    if spec is None:
        return None
    for name in GENERATION_PAGED_METHODS:
        if getattr(clazz, name, None) is getattr(BaseModel, name):
            return None
    return spec


#: counter-based RNG roles shared by every sampled draw (models/lm.py)
ROLE_TARGET = 0
ROLE_DRAFT = 1
ROLE_ACCEPT = 2

#: the sampled-decode methods (real temperature/top-k/top-p sampling).
#: ``decode_step_sampled`` is the base requirement; paged-capable
#: templates must also wire the paged variant or sampling stays off.
GENERATION_SAMPLING_METHODS = ("decode_step_sampled",
                               "paged_decode_step_sampled")

#: the one extra method of the speculative-verify contract
GENERATION_SPEC_METHODS = ("paged_verify_step",)


def sampling_capability(clazz: type) -> Optional[GenerationSpec]:
    """The template's :class:`GenerationSpec` iff real sampling is fully
    wired: the base generation contract plus ``decode_step_sampled``, and
    — when the template is paged-capable — ``paged_decode_step_sampled``
    too (the worker serves whichever plane the template supports; a
    sampled method the serving plane can't reach is half-wired). None
    degrades to greedy-only serving: the worker turns a sampled request
    against it into a typed request error, never a silent greedy answer."""
    spec = generation_capability(clazz)
    if spec is None:
        return None
    needed = ["decode_step_sampled"]
    if paged_generation_capability(clazz) is not None:
        needed.append("paged_decode_step_sampled")
    import logging

    for name in needed:
        if getattr(clazz, name, None) is getattr(BaseModel, name):
            logging.getLogger(__name__).warning(
                "%s does not override %s(); template is NOT "
                "sampling-capable — sampled requests will be refused",
                clazz.__name__, name)
            return None
    return spec


def draft_capability(clazz: type) -> Optional[GenerationSpec]:
    """The template's :class:`GenerationSpec` iff it can serve as a
    speculative DRAFT model: the base (ring) generation contract plus
    ``decode_step_sampled`` — drafts propose through their own contiguous
    ring (a small model's worst-case K/V is cheap) and must return their
    full modified distribution q for the accept test.

    A draft may ALSO provide ``decode_steps_sampled(cache, ids,
    positions, k, sampling) -> (tokens (S, k), q (S, k, V), cache)`` —
    the whole k-token proposal burst fused into one program. Optional
    fast path, not part of the capability: the worker falls back to k
    chained ``decode_step_sampled`` calls (each paying dispatch plus a
    host sync) when it is absent."""
    spec = generation_capability(clazz)
    if spec is None:
        return None
    if getattr(clazz, "decode_step_sampled", None) is \
            getattr(BaseModel, "decode_step_sampled"):
        import logging

        logging.getLogger(__name__).warning(
            "%s does not override decode_step_sampled(); template cannot "
            "serve as a speculative draft model", clazz.__name__)
        return None
    return spec


def spec_verify_capability(clazz: type) -> Optional[GenerationSpec]:
    """The template's :class:`GenerationSpec` iff it can serve as a
    speculative TARGET: paged-capable, sampling-capable, and
    ``paged_verify_step`` overridden. None degrades the worker to plain
    paged decode (a safe fallback, surfaced by the doctor's speculative-
    decoding check and the worker's ``gen_spec_degraded`` stats field)."""
    spec = paged_generation_capability(clazz)
    if spec is None:
        return None
    if sampling_capability(clazz) is None:
        return None
    if getattr(clazz, "paged_verify_step", None) is \
            getattr(BaseModel, "paged_verify_step"):
        import logging

        logging.getLogger(__name__).warning(
            "%s does not override paged_verify_step(); template cannot "
            "verify speculative drafts — serving plain paged decode",
            clazz.__name__)
        return None
    return spec


def load_model_class(
    model_bytes: bytes, class_name: str, temp_dir: Optional[str] = None
) -> type:
    """Import an uploaded model template's ``.py`` bytes and return its class
    (reference rafiki/model/model.py:221-242)."""
    tmp = tempfile.NamedTemporaryFile(
        "wb", suffix=".py", dir=temp_dir, delete=False
    )
    try:
        tmp.write(model_bytes)
        tmp.close()
        mod_name = f"rafiki_model_{os.path.basename(tmp.name)[:-3]}"
        spec = importlib.util.spec_from_file_location(mod_name, tmp.name)
        assert spec is not None and spec.loader is not None
        module = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = module
        spec.loader.exec_module(module)
        clazz = getattr(module, class_name, None)
        if clazz is None or not inspect.isclass(clazz):
            raise InvalidModelClassError(
                f"Class {class_name!r} not found in uploaded model file"
            )
        if not issubclass(clazz, BaseModel):
            raise InvalidModelClassError(
                f"{class_name} must subclass rafiki_tpu BaseModel"
            )
        return clazz
    finally:
        try:
            os.unlink(tmp.name)
        except OSError:
            pass


def validate_model_dependencies(clazz: type) -> List[str]:
    """Check declared dependencies are importable in this environment;
    return the missing ones. Provisioning (the reference's
    install-command synthesis, reference rafiki/model/model.py:244-273)
    lives in sdk/deps.py behind RAFIKI_INSTALL_DEPS."""
    from rafiki_tpu.sdk.deps import missing_dependencies

    return missing_dependencies(getattr(clazz, "dependencies", {}) or {})


def test_model_class(
    model_file_path: Optional[str] = None,
    model_class: Optional[str] = None,
    task: Optional[str] = None,
    dependencies: Optional[Dict[str, Optional[str]]] = None,
    train_dataset_uri: Optional[str] = None,
    test_dataset_uri: Optional[str] = None,
    queries: Optional[List[Any]] = None,
    clazz: Optional[type] = None,
    knobs: Optional[Dict[str, Any]] = None,
) -> List[Any]:
    """Local contract-conformance harness (reference rafiki/model/model.py:129-219).

    Runs the full lifecycle a deployed trial would: dependency check ->
    knob-config check -> in-process advisor proposal -> train -> evaluate ->
    parameter dump/restore round-trip through bytes -> destroy + fresh
    instance -> predict -> JSON-serializability check -> ensembling smoke
    test. Returns the predictions.

    Call with either ``clazz=`` (an already-imported class) or
    ``model_file_path=`` + ``model_class=``.
    """
    from rafiki_tpu.advisor.advisor import Advisor
    from rafiki_tpu.predictor.ensemble import ensemble_predictions
    from rafiki_tpu.sdk.params import dump_params, load_params

    if clazz is None:
        assert model_file_path is not None and model_class is not None
        with open(model_file_path, "rb") as f:
            clazz = load_model_class(f.read(), model_class)

    missing = validate_model_dependencies(clazz)
    if missing:
        raise InvalidModelClassError(f"Missing dependencies: {missing}")

    knob_config = clazz.get_knob_config()
    for name, knob in knob_config.items():
        if not isinstance(knob, BaseKnob):
            raise InvalidModelClassError(f"Knob {name!r} is not a BaseKnob")
    # knob config must survive the HTTP wire format
    serialize_knob_config(knob_config)

    if knobs is None:
        advisor = Advisor(knob_config)
        knobs = advisor.propose()
    validate_knobs(knob_config, knobs)
    print(f"[test_model_class] knobs: {knobs}")

    model = clazz(**knobs)
    assert train_dataset_uri is not None and test_dataset_uri is not None
    model.train(train_dataset_uri)
    score = model.evaluate(test_dataset_uri)
    try:
        score = float(score)  # accepts python/numpy/jax scalars alike
    except (TypeError, ValueError):
        raise InvalidModelClassError("evaluate() must return a float score")
    print(f"[test_model_class] score: {score}")

    # round-trip parameters through bytes, as the worker/predictor would
    params_bytes = dump_params(model.dump_parameters())
    model.destroy()

    model = clazz(**knobs)
    model.load_parameters(load_params(params_bytes))

    queries = queries if queries is not None else []
    predictions = model.predict(queries)
    if not isinstance(predictions, list) or len(predictions) != len(queries):
        raise InvalidModelClassError("predict() must return one prediction per query")
    try:
        json.dumps(predictions)
    except (TypeError, ValueError) as e:
        raise InvalidModelClassError(f"Predictions not JSON-serializable: {e}")

    if queries:
        # ensembling smoke test across two copies of the same predictions
        ensemble_predictions([predictions, predictions], task)

    model.destroy()
    print("[test_model_class] OK")
    return predictions
