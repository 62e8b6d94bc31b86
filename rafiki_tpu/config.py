"""Code-level configuration constants (analogue of reference rafiki/config.py).

Environment-variable-first, mirroring the reference's config tiers
(SURVEY.md §5.6): deployment config comes from the environment; these are the
in-code defaults. Path-like values are resolved *lazily* (module
``__getattr__``) so tests and the placement layer can repoint
``RAFIKI_WORKDIR`` at runtime.
"""

import os


def _env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, default))


def _env_float(name: str, default: float) -> float:
    return float(os.environ.get(name, default))


SUPERADMIN_EMAIL = os.environ.get("SUPERADMIN_EMAIL", "superadmin@rafiki")
SUPERADMIN_PASSWORD = os.environ.get("SUPERADMIN_PASSWORD", "rafiki")

APP_SECRET = os.environ.get("APP_SECRET", "rafiki-tpu-dev-secret")
TOKEN_TTL_HOURS = _env_int("TOKEN_TTL_HOURS", 24)

# Serving fleet shape per inference job — reference parity: 2 best trials
# x 2 replicas each (reference rafiki/config.py:10-11). The predictor
# load-balances within a trial's replicas and ensembles across trials.
INFERENCE_MAX_BEST_TRIALS = _env_int("INFERENCE_MAX_BEST_TRIALS", 2)
INFERENCE_WORKER_REPLICAS_PER_TRIAL = _env_int(
    "INFERENCE_WORKER_REPLICAS_PER_TRIAL", 2
)

# Continuous-batching predictor knobs. The reference's serving pipeline had a
# hard p50 floor of ~0.25-0.5 s from sleep-polling (reference rafiki/config.py:14,17
# and predictor/predictor.py:46-59); here queries are handed to the batcher via
# condition variables and flushed either when the batch fills or after
# PREDICT_BATCH_DEADLINE_MS, whichever is first. Deadline 0 = serve whatever
# has queued the moment the worker is free: under load batches fill by
# themselves (queries accumulate during the previous dispatch — continuous
# batching self-paces), so an artificial coalescing wait only adds latency
# at low load. Multi-query requests stay one batch via submit_many. Raise
# the deadline only if single-query clients swamp dispatch overhead.
PREDICT_MAX_BATCH_SIZE = _env_int("PREDICT_MAX_BATCH_SIZE", 64)
PREDICT_BATCH_DEADLINE_MS = _env_float("PREDICT_BATCH_DEADLINE_MS", 0.0)
PREDICT_TIMEOUT_S = _env_float("PREDICT_TIMEOUT_S", 30.0)

# -- serving-plane overload control (docs/failure-model.md, "Overload
# faults"). All four knobs resolve lazily (module __getattr__ below) so
# tests and operators can retune a live deployment's next queue/server
# without re-importing:
#   RAFIKI_PREDICT_QUEUE_DEPTH      per-worker inbox cap; submits beyond it
#                                   raise QueueFullError -> the doors shed
#                                   with 429 + Retry-After instead of
#                                   growing an unbounded backlog (0 = uncapped)
#   RAFIKI_PREDICT_MAX_INFLIGHT     concurrently-admitted requests per
#                                   serving door; excess is shed with 503
#                                   before it can pile up handler threads
#                                   (0 = unbounded)
#   RAFIKI_PREDICT_HEDGE_SUPPRESS_DEPTH
#                                   a sibling replica whose queue depth
#                                   exceeds this never receives a hedge
#                                   batch — duplicate work onto an already
#                                   saturated replica is how overload
#                                   metastasizes ("The Tail at Scale")
#   RAFIKI_PREDICT_DRAIN_S          PredictorServer.stop() waits this long
#                                   for in-flight handlers before closing

DEFAULT_TRIAL_COUNT = _env_int("DEFAULT_TRIAL_COUNT", 5)

ADMIN_HOST = os.environ.get("ADMIN_HOST", "127.0.0.1")
ADMIN_PORT = _env_int("ADMIN_PORT", 3000)

SERVICE_DEPLOY_TIMEOUT_S = _env_float("SERVICE_DEPLOY_TIMEOUT_S", 60.0)

# -- fleet health (docs/failure-model.md) -----------------------------------
# Heartbeats: the admin-side HostAgentPlacementManager probes each agent's
# /healthz every AGENT_HEARTBEAT_INTERVAL_S; AGENT_DOWN_THRESHOLD
# consecutive misses marks the host DOWN (queues evicted, services
# errored/rescheduled). 0 disables the monitor thread.
AGENT_HEARTBEAT_INTERVAL_S = _env_float("RAFIKI_AGENT_HEARTBEAT_S", 5.0)
AGENT_DOWN_THRESHOLD = _env_int("RAFIKI_AGENT_DOWN_THRESHOLD", 3)
AGENT_HEARTBEAT_TIMEOUT_S = _env_float("RAFIKI_AGENT_HEARTBEAT_TIMEOUT_S", 2.0)
# Transport retry (idempotent agent calls only): up to AGENT_RETRY_MAX
# re-attempts on transport failure, exponential backoff from
# AGENT_RETRY_BACKOFF_S with full jitter.
AGENT_RETRY_MAX = _env_int("RAFIKI_AGENT_RETRY_MAX", 2)
AGENT_RETRY_BACKOFF_S = _env_float("RAFIKI_AGENT_RETRY_BACKOFF_S", 0.1)
# Circuit breaker: AGENT_BREAKER_THRESHOLD consecutive transport failures
# open an agent's circuit; calls then fail fast (no 10 s socket timeout)
# until a half-open probe succeeds after AGENT_BREAKER_COOLDOWN_S.
AGENT_BREAKER_THRESHOLD = _env_int("RAFIKI_AGENT_BREAKER_THRESHOLD", 3)
AGENT_BREAKER_COOLDOWN_S = _env_float("RAFIKI_AGENT_BREAKER_COOLDOWN_S", 5.0)


def workdir() -> str:
    return os.environ.get("RAFIKI_WORKDIR", os.path.abspath("."))


# Filesystem layout (shared volume in the reference, local dirs here).
# Resolved lazily against the current environment on every access.
_DYNAMIC_PATHS = {
    "WORKDIR": lambda: workdir(),
    "DATA_DIR": lambda: os.environ.get(
        "RAFIKI_DATA_DIR", os.path.join(workdir(), "data")
    ),
    "PARAMS_DIR": lambda: os.environ.get(
        "RAFIKI_PARAMS_DIR", os.path.join(workdir(), "params")
    ),
    "LOGS_DIR": lambda: os.environ.get(
        "RAFIKI_LOGS_DIR", os.path.join(workdir(), "logs")
    ),
    # connection string: RAFIKI_DB_URL (e.g. postgresql://...) wins over the
    # sqlite file path, so EVERY call site that passes config.DB_PATH honors
    # the URL
    "DB_PATH": lambda: (
        os.environ.get("RAFIKI_DB_URL")
        or os.environ.get("RAFIKI_DB_PATH")
        or os.path.join(workdir(), "rafiki.sqlite3")
    ),
    # per-job predictor listeners: lazily resolved so a deployment (or a
    # test) can flip RAFIKI_PREDICTOR_PORTS before deploying a job
    "PREDICTOR_PORTS": lambda: (
        os.environ.get("RAFIKI_PREDICTOR_PORTS", "0") == "1"),
    "PREDICTOR_HOST": lambda: (
        os.environ.get("RAFIKI_PREDICTOR_HOST", "127.0.0.1")),
    # overload-control knobs (commented where declared above)
    "PREDICT_QUEUE_DEPTH": lambda: _env_int(
        "RAFIKI_PREDICT_QUEUE_DEPTH", 256),
    "PREDICT_MAX_INFLIGHT": lambda: _env_int(
        "RAFIKI_PREDICT_MAX_INFLIGHT", 64),
    "PREDICT_HEDGE_SUPPRESS_DEPTH": lambda: _env_int(
        "RAFIKI_PREDICT_HEDGE_SUPPRESS_DEPTH", PREDICT_MAX_BATCH_SIZE),
    "PREDICT_DRAIN_S": lambda: _env_float("RAFIKI_PREDICT_DRAIN_S", 5.0),
    # -- prediction result cache + single-flight coalescing (docs/
    # performance.md "Prediction caching & single-flight"). Lazy so a
    # live deployment's NEXT request picks up a retune. OFF by default:
    # serving identical answers to identical queries is a behavior
    # change the operator opts into (a template whose predict is
    # deliberately stochastic would be silently de-randomized):
    #   RAFIKI_PREDICT_CACHE=1          serve repeated identical queries
    #                                   from a bounded in-process cache
    #                                   keyed (query digest, job, served
    #                                   model version) — invalidated on
    #                                   deploy/rollback/recovery
    #                                   adoption, excluded for
    #                                   TEXT_GENERATION and ensembled-
    #                                   stochastic jobs
    #   RAFIKI_PREDICT_CACHE_TTL_S=30   entry lifetime; <=0 disables
    #                                   fills (doctor WARNs with the
    #                                   cache on)
    #   RAFIKI_PREDICT_CACHE_MAX_BYTES=67108864  byte cap, LRU-evicted
    #                                   (doctor WARNs past the host-
    #                                   memory heuristic)
    #   RAFIKI_PREDICT_SINGLEFLIGHT=1   0 = concurrent identical misses
    #                                   each pay their own forward
    #                                   instead of sharing the leader's
    #                                   (only consulted while the cache
    #                                   is on)
    "PREDICT_CACHE": lambda: os.environ.get(
        "RAFIKI_PREDICT_CACHE", "0") == "1",
    "PREDICT_CACHE_TTL_S": lambda: _env_float(
        "RAFIKI_PREDICT_CACHE_TTL_S", 30.0),
    "PREDICT_CACHE_MAX_BYTES": lambda: _env_int(
        "RAFIKI_PREDICT_CACHE_MAX_BYTES", 64 * 1024 * 1024),
    "PREDICT_SINGLEFLIGHT": lambda: os.environ.get(
        "RAFIKI_PREDICT_SINGLEFLIGHT", "1") != "0",
    # -- control-plane crash recovery (docs/failure-model.md, "Control-
    # plane faults"). A fresh Admin on an existing store reconciles the
    # DB against what is actually running before opening its doors:
    #   RAFIKI_RECOVER_ADOPT=1            0 = never adopt surviving
    #                                     workers on restart; they are
    #                                     fenced (stopped) and train
    #                                     services rescheduled instead
    #                                     (doctor WARNs while set)
    #   RAFIKI_RECOVER_PROBE_TIMEOUT_S=5  per-agent inventory probe budget
    #   RAFIKI_RECOVER_RETRY_MAX=4        metadata-store retries during
    #                                     reconcile (bounded, jittered)
    #   RAFIKI_RECOVER_RETRY_BACKOFF_S=0.2  backoff base for those retries
    # -- training-plane trial fault tolerance (docs/failure-model.md,
    # "Training-plane faults"). Lazy so tests/operators retune a live
    # worker's NEXT trial without re-importing:
    #   RAFIKI_TRIAL_RETRY_MAX=2        infra-class faults (INFRA/MEM/
    #                                   STALL) re-run under the same
    #                                   trial id up to this many times
    #                                   (0 = every fault burns budget;
    #                                   doctor WARNs)
    #   RAFIKI_TRIAL_RETRY_BACKOFF_S=0.5  backoff base for those
    #                                   re-runs (exponential, jittered)
    #   RAFIKI_TRIAL_QUARANTINE_K=3     user-class faults on near-
    #                                   identical knobs before that
    #                                   signature is quarantined
    #   RAFIKI_TRIAL_REPROPOSE_MAX=8    proposals rejected per slot for
    #                                   matching a quarantined signature
    #                                   before the worker accepts one
    #   RAFIKI_TRIAL_FAULT_LIMIT=5      consecutive user-class faults on
    #                                   DISTINCT knobs that error the
    #                                   whole job early (0 disables)
    #   RAFIKI_PENDING_FEEDBACK_MAX=256 cap on queued advisor feedback
    #                                   awaiting retry (drop-oldest)
    # (RAFIKI_TRIAL_STALL_S lives in sdk/sandbox.py: the no-frame
    # deadline on sandbox children.)
    # -- vectorized trial execution (docs/performance.md, "Vectorized
    # trial execution"). Lazy like the other trial knobs:
    #   RAFIKI_TRIAL_VMAP=1           0 = kill switch: never train a
    #                                 population of proposals as one
    #                                 vmapped program, even for templates
    #                                 that advertise population_spec
    #   RAFIKI_TRIAL_VMAP_K=4         proposals drained per vectorized
    #                                 round (also settable per job via
    #                                 budget TRIAL_VMAP_K; capped by the
    #                                 template's PopulationSpec
    #                                 max_members); <2 disables in effect
    "TRIAL_VMAP": lambda: os.environ.get("RAFIKI_TRIAL_VMAP", "1") != "0",
    "TRIAL_VMAP_K": lambda: _env_int("RAFIKI_TRIAL_VMAP_K", 4),
    "TRIAL_RETRY_MAX": lambda: _env_int("RAFIKI_TRIAL_RETRY_MAX", 2),
    "TRIAL_RETRY_BACKOFF_S": lambda: _env_float(
        "RAFIKI_TRIAL_RETRY_BACKOFF_S", 0.5),
    "TRIAL_QUARANTINE_K": lambda: _env_int("RAFIKI_TRIAL_QUARANTINE_K", 3),
    "TRIAL_REPROPOSE_MAX": lambda: _env_int("RAFIKI_TRIAL_REPROPOSE_MAX", 8),
    "TRIAL_FAULT_LIMIT": lambda: _env_int("RAFIKI_TRIAL_FAULT_LIMIT", 5),
    "PENDING_FEEDBACK_MAX": lambda: _env_int(
        "RAFIKI_PENDING_FEEDBACK_MAX", 256),
    # -- elastic serving autoscaler (docs/failure-model.md, "Overload
    # adaptation"). All knobs resolve lazily so tests and operators can
    # retune a live control loop; the loop itself is OFF by default —
    # existing deployments keep their static replica counts:
    #   RAFIKI_AUTOSCALE=1              start the admin-side control loop
    #   RAFIKI_AUTOSCALE_INTERVAL_S=2   decision-loop tick interval
    #   RAFIKI_AUTOSCALE_WINDOW_S=15    signal window a decision looks at
    #   RAFIKI_AUTOSCALE_SHED_THRESHOLD=3   shed events inside the window
    #                                   that read "sustained overload"
    #   RAFIKI_AUTOSCALE_DEPTH_HIGH=8   mean backlog depth that scales up
    #   RAFIKI_AUTOSCALE_DEPTH_LOW=1    max backlog depth that still
    #                                   counts as idle (hysteresis: LOW
    #                                   must sit well under HIGH)
    #   RAFIKI_AUTOSCALE_MIN_REPLICAS=1 never drain below this many live
    #                                   replicas per job
    #   RAFIKI_AUTOSCALE_MAX_REPLICAS=8 never grow past this many
    #   RAFIKI_AUTOSCALE_STEP=1         replicas per decision (bounded
    #                                   step — the loop cannot stampede)
    #   RAFIKI_AUTOSCALE_COOLDOWN_UP_S=5    quiet time after ANY action
    #                                   before the next scale-up
    #   RAFIKI_AUTOSCALE_COOLDOWN_DOWN_S=30 ... before the next
    #                                   scale-down (longer: flapping down
    #                                   is worse than holding spare
    #                                   capacity a little while)
    #   RAFIKI_AUTOSCALE_DRAIN_S=10     bounded graceful-drain window per
    #                                   removed replica (stop admitting,
    #                                   flush its queue, then destroy)
    #   RAFIKI_AUTOSCALE_TRAIN_FLOOR=1  chips the serving plane may never
    #                                   borrow into: at least this many
    #                                   chips stay free (or training's)
    #                                   whatever the surge
    #   RAFIKI_AUTOSCALE_FAIR=1         per-job weighted fair admission at
    #                                   shared doors (off by default)
    #   RAFIKI_AUTOSCALE_FAIR_WINDOW_S=10   half-life of the per-tenant
    #                                   admitted-query charge decay
    #   RAFIKI_AUTOSCALE_FAIR_BURST=32  admitted queries a tenant may run
    #                                   past its fair share before 429s
    #   RAFIKI_AUTOSCALE_FAIR_WEIGHTS=  "appA=3,appB=1" (unlisted
    #                                   tenants weigh 1)
    # -- generative serving (docs/serving-generation.md). Lazy like the
    # other serving knobs so a live deployment's NEXT worker/stream picks
    # up a retune:
    #   RAFIKI_GEN_MAX_SLOTS=8          co-resident sequences per
    #                                   generation worker (the KV cache is
    #                                   preallocated at this width; doctor
    #                                   WARNs past the memory heuristic)
    #   RAFIKI_GEN_MAX_TOKENS=64        per-request decode budget cap (a
    #                                   request asking more is clamped)
    #   RAFIKI_GEN_STREAM_TIMEOUT_S=10  door-side inter-token stall
    #                                   timeout: a stream with no delta
    #                                   for this long ends with a typed
    #                                   terminal error frame
    #   RAFIKI_GEN_OCCUPANCY_HIGH=0.85  mean slot occupancy over the
    #                                   autoscaler window that reads
    #                                   "generation slots saturated" and
    #                                   scales the job up
    #   RAFIKI_GEN_KV_PAGED=1           0 = legacy contiguous ring per
    #                                   slot (the A/B baseline); 1 = the
    #                                   block/paged KV allocator for
    #                                   templates that advertise the
    #                                   paged methods (worker/kv_paging)
    #   RAFIKI_GEN_KV_BLOCK_TOKENS=16   K/V rows per pool page — the
    #                                   paging granularity (doctor WARNs
    #                                   on degenerate sizes)
    #   RAFIKI_GEN_KV_POOL_BLOCKS=0     pages in the pool; 0 = auto-size
    #                                   to the legacy ring's capacity
    #                                   (slots x ceil(max_context/block))
    #                                   so paged-vs-ring A/B runs at
    #                                   equal KV memory
    #   RAFIKI_GEN_PREFIX_CACHE=1       0 = never share prompt-prefix
    #                                   blocks across streams (hit/miss
    #                                   counters and the doctor surface a
    #                                   disabled cache under shared-
    #                                   prefix traffic)
    #   RAFIKI_GEN_PREFILL_CHUNK=64     prompt tokens ingested per
    #                                   scheduler round (paged path): a
    #                                   long-prompt join interleaves with
    #                                   decode rounds instead of stalling
    #                                   resident streams (0 = one-shot
    #                                   prefill)
    #   RAFIKI_GEN_SAMPLING=1           0 = greedy-only serving: requests
    #                                   carrying temperature/top_k/top_p/
    #                                   seed get a typed 400 instead of a
    #                                   silent greedy answer (kill switch)
    #   RAFIKI_GEN_SPEC=1               0 = never speculate; 1 = draft-
    #                                   verify speculative decoding on the
    #                                   paged path whenever the job has a
    #                                   draft model (GEN_DRAFT_TRIAL
    #                                   budget) and the template verifies
    #   RAFIKI_GEN_SPEC_K=4             draft tokens proposed per round;
    #                                   the verify forward is k+1 wide,
    #                                   so k also sizes the per-round KV
    #                                   write burst (doctor WARNs past 8)
    #   RAFIKI_GEN_SPEC_MIN_RATE=0.3    acceptance rate below which the
    #                                   doctor reads "the draft is not
    #                                   earning its keep" (observability
    #                                   threshold only — serving never
    #                                   auto-disables on it)
    # -- stream continuity (docs/failure-model.md "Stream continuity"):
    # the door journals each live stream and resumes it on a sibling
    # replica when its replica dies or hands the stream back:
    #   RAFIKI_GEN_RESUME_MAX=3         resume attempts per stream before
    #                                   the fault surfaces to the client
    #                                   (0 disables resume entirely —
    #                                   drain handoffs then become
    #                                   client-visible errors; doctor
    #                                   WARNs with the autoscaler on)
    #   RAFIKI_GEN_RESUME_BACKOFF_S=0.05  base of the jittered resume
    #                                   backoff (attempt n sleeps up to
    #                                   base*2^n, capped by the request
    #                                   deadline)
    #   RAFIKI_GEN_JOURNAL_MAX_KB=64    per-stream journal byte cap
    #                                   (prompt + committed tokens); a
    #                                   stream outgrowing it keeps
    #                                   streaming but loses resume
    #                                   eligibility (doctor WARNs when
    #                                   the cap cannot hold a worst-case
    #                                   GEN_MAX_TOKENS stream)
    #   RAFIKI_GEN_JOURNAL_TTL_S=600    journal entry TTL: a stream older
    #                                   than this is never resumed (a
    #                                   wedged multi-hour stream must not
    #                                   replay forever)
    "GEN_MAX_SLOTS": lambda: _env_int("RAFIKI_GEN_MAX_SLOTS", 8),
    "GEN_SAMPLING": lambda: os.environ.get(
        "RAFIKI_GEN_SAMPLING", "1") != "0",
    "GEN_SPEC": lambda: os.environ.get("RAFIKI_GEN_SPEC", "1") != "0",
    "GEN_SPEC_K": lambda: _env_int("RAFIKI_GEN_SPEC_K", 4),
    "GEN_SPEC_MIN_RATE": lambda: _env_float(
        "RAFIKI_GEN_SPEC_MIN_RATE", 0.3),
    "GEN_KV_PAGED": lambda: os.environ.get(
        "RAFIKI_GEN_KV_PAGED", "1") != "0",
    "GEN_KV_BLOCK_TOKENS": lambda: _env_int(
        "RAFIKI_GEN_KV_BLOCK_TOKENS", 16),
    "GEN_KV_POOL_BLOCKS": lambda: _env_int("RAFIKI_GEN_KV_POOL_BLOCKS", 0),
    "GEN_PREFIX_CACHE": lambda: os.environ.get(
        "RAFIKI_GEN_PREFIX_CACHE", "1") != "0",
    "GEN_PREFILL_CHUNK": lambda: _env_int("RAFIKI_GEN_PREFILL_CHUNK", 64),
    "GEN_MAX_TOKENS": lambda: _env_int("RAFIKI_GEN_MAX_TOKENS", 64),
    "GEN_STREAM_TIMEOUT_S": lambda: _env_float(
        "RAFIKI_GEN_STREAM_TIMEOUT_S", 10.0),
    "GEN_OCCUPANCY_HIGH": lambda: _env_float(
        "RAFIKI_GEN_OCCUPANCY_HIGH", 0.85),
    "GEN_RESUME_MAX": lambda: _env_int("RAFIKI_GEN_RESUME_MAX", 3),
    "GEN_RESUME_BACKOFF_S": lambda: _env_float(
        "RAFIKI_GEN_RESUME_BACKOFF_S", 0.05),
    "GEN_JOURNAL_MAX_KB": lambda: _env_int(
        "RAFIKI_GEN_JOURNAL_MAX_KB", 64),
    "GEN_JOURNAL_TTL_S": lambda: _env_float(
        "RAFIKI_GEN_JOURNAL_TTL_S", 600.0),
    "AUTOSCALE": lambda: os.environ.get("RAFIKI_AUTOSCALE", "0") == "1",
    "AUTOSCALE_INTERVAL_S": lambda: _env_float(
        "RAFIKI_AUTOSCALE_INTERVAL_S", 2.0),
    "AUTOSCALE_WINDOW_S": lambda: _env_float(
        "RAFIKI_AUTOSCALE_WINDOW_S", 15.0),
    "AUTOSCALE_SHED_THRESHOLD": lambda: _env_int(
        "RAFIKI_AUTOSCALE_SHED_THRESHOLD", 3),
    "AUTOSCALE_DEPTH_HIGH": lambda: _env_float(
        "RAFIKI_AUTOSCALE_DEPTH_HIGH", 8.0),
    "AUTOSCALE_DEPTH_LOW": lambda: _env_float(
        "RAFIKI_AUTOSCALE_DEPTH_LOW", 1.0),
    "AUTOSCALE_MIN_REPLICAS": lambda: _env_int(
        "RAFIKI_AUTOSCALE_MIN_REPLICAS", 1),
    "AUTOSCALE_MAX_REPLICAS": lambda: _env_int(
        "RAFIKI_AUTOSCALE_MAX_REPLICAS", 8),
    "AUTOSCALE_STEP": lambda: _env_int("RAFIKI_AUTOSCALE_STEP", 1),
    "AUTOSCALE_COOLDOWN_UP_S": lambda: _env_float(
        "RAFIKI_AUTOSCALE_COOLDOWN_UP_S", 5.0),
    "AUTOSCALE_COOLDOWN_DOWN_S": lambda: _env_float(
        "RAFIKI_AUTOSCALE_COOLDOWN_DOWN_S", 30.0),
    "AUTOSCALE_DRAIN_S": lambda: _env_float("RAFIKI_AUTOSCALE_DRAIN_S", 10.0),
    "AUTOSCALE_TRAIN_FLOOR": lambda: _env_int(
        "RAFIKI_AUTOSCALE_TRAIN_FLOOR", 1),
    "AUTOSCALE_FAIR": lambda: os.environ.get(
        "RAFIKI_AUTOSCALE_FAIR", "0") == "1",
    "AUTOSCALE_FAIR_WINDOW_S": lambda: _env_float(
        "RAFIKI_AUTOSCALE_FAIR_WINDOW_S", 10.0),
    "AUTOSCALE_FAIR_BURST": lambda: _env_float(
        "RAFIKI_AUTOSCALE_FAIR_BURST", 32.0),
    "AUTOSCALE_FAIR_WEIGHTS": lambda: os.environ.get(
        "RAFIKI_AUTOSCALE_FAIR_WEIGHTS", ""),
    # -- cold-start resilience (docs/failure-model.md "Cold-start
    # faults"). The persistent XLA executable cache makes a replacement
    # process's jit programs a disk read instead of a compile; the warm
    # standby pool makes scale-up/replacement an add_worker route instead
    # of a deploy. Lazy like every serving knob:
    #   RAFIKI_COMPILE_CACHE=1          0 disables the persistent compile
    #                                   cache everywhere (workers still
    #                                   warm up, every boot is cold)
    #   RAFIKI_COMPILE_CACHE_DIR=       shared executable-cache dir
    #                                   (default <checkout>/xla_cache);
    #                                   JAX_COMPILATION_CACHE_DIR, where
    #                                   set, wins and is used as it is —
    #                                   see sdk/compile_cache.py
    #   RAFIKI_COMPILE_CACHE_CPU=1      opt the CPU backend in (entries
    #                                   are machine-feature-tied; safe on
    #                                   one box, default off)
    #   RAFIKI_COMPILE_CACHE_MIN_COMPILE_S=0.5  only persist programs
    #                                   whose compile took at least this
    #                                   long (0 = persist everything —
    #                                   what the drills/bench use on CPU)
    #   RAFIKI_COMPILE_WARM_THRESHOLD_S=1.0  warm/cold classification
    #                                   fallback when the JAX cache-event
    #                                   listeners are unavailable: a boot
    #                                   whose total warm-up compile time
    #                                   stays under this reads warm
    #   RAFIKI_AUTOSCALE_WARM_POOL=0    K pre-loaded, pre-warmed standby
    #                                   replicas kept per RUNNING
    #                                   inference job (0 = off). Standbys
    #                                   hold chips via the arbiter's
    #                                   borrow book: the training floor
    #                                   still outranks them and reclaim
    #                                   drains them FIRST
    #   RAFIKI_AUTOSCALE_WARM_POOL_INTERVAL_S=5  maintenance-loop tick
    #   RAFIKI_AUTOSCALE_WARM_RETRY_MAX=3  consecutive standby-placement
    #                                   failures per job before the pool
    #                                   reports that job degraded and
    #                                   pauses retries
    #   RAFIKI_AUTOSCALE_WARM_RETRY_COOLDOWN_S=30  how long a degraded
    #                                   job's refill stays paused
    "COMPILE_CACHE": lambda: os.environ.get(
        "RAFIKI_COMPILE_CACHE", "1") != "0",
    "COMPILE_CACHE_DIR": lambda: os.environ.get(
        "RAFIKI_COMPILE_CACHE_DIR", ""),
    "COMPILE_CACHE_CPU": lambda: os.environ.get(
        "RAFIKI_COMPILE_CACHE_CPU", "") != "",
    "COMPILE_CACHE_MIN_COMPILE_S": lambda: _env_float(
        "RAFIKI_COMPILE_CACHE_MIN_COMPILE_S", 0.5),
    "COMPILE_WARM_THRESHOLD_S": lambda: _env_float(
        "RAFIKI_COMPILE_WARM_THRESHOLD_S", 1.0),
    "AUTOSCALE_WARM_POOL": lambda: _env_int(
        "RAFIKI_AUTOSCALE_WARM_POOL", 0),
    "AUTOSCALE_WARM_POOL_INTERVAL_S": lambda: _env_float(
        "RAFIKI_AUTOSCALE_WARM_POOL_INTERVAL_S", 5.0),
    "AUTOSCALE_WARM_RETRY_MAX": lambda: _env_int(
        "RAFIKI_AUTOSCALE_WARM_RETRY_MAX", 3),
    "AUTOSCALE_WARM_RETRY_COOLDOWN_S": lambda: _env_float(
        "RAFIKI_AUTOSCALE_WARM_RETRY_COOLDOWN_S", 30.0),
    # -- safe live rollouts (docs/failure-model.md "Rollout faults").
    # admin/rollout.py updates a RUNNING inference job to a new trial in
    # place: one canary replica judged over a trailing window, then a
    # rolling replace in bounded batches, with automatic rollback on SLO
    # breach / canary crash / deploy timeout. Lazy so a live rollout's
    # NEXT phase picks up a retune:
    #   RAFIKI_ROLLOUT_CANARY_FRACTION=0.1  traffic fraction routed to
    #                                   the canary replica while it is
    #                                   judged (0..1)
    #   RAFIKI_ROLLOUT_JUDGE_WINDOW_S=10  trailing window the SLO judge
    #                                   compares canary vs incumbent over
    #   RAFIKI_ROLLOUT_MIN_REQUESTS=5   canary requests needed before an
    #                                   error-rate/latency verdict counts
    #                                   (an idle job proceeds after
    #                                   3x the window with a low-traffic
    #                                   note instead of stalling forever)
    #   RAFIKI_ROLLOUT_ERR_DELTA=0.1    max (canary - incumbent) error
    #                                   rate before automatic rollback
    #   RAFIKI_ROLLOUT_P95_FACTOR=3.0   canary p95 past incumbent p95 x
    #                                   this factor is an SLO breach
    #   RAFIKI_ROLLOUT_BATCH=1          replicas replaced per rolling
    #                                   batch (place new, drain old)
    "ROLLOUT_CANARY_FRACTION": lambda: _env_float(
        "RAFIKI_ROLLOUT_CANARY_FRACTION", 0.1),
    "ROLLOUT_JUDGE_WINDOW_S": lambda: _env_float(
        "RAFIKI_ROLLOUT_JUDGE_WINDOW_S", 10.0),
    "ROLLOUT_MIN_REQUESTS": lambda: _env_int(
        "RAFIKI_ROLLOUT_MIN_REQUESTS", 5),
    "ROLLOUT_ERR_DELTA": lambda: _env_float(
        "RAFIKI_ROLLOUT_ERR_DELTA", 0.1),
    "ROLLOUT_P95_FACTOR": lambda: _env_float(
        "RAFIKI_ROLLOUT_P95_FACTOR", 3.0),
    "ROLLOUT_BATCH": lambda: _env_int("RAFIKI_ROLLOUT_BATCH", 1),
    "RECOVER_ADOPT": lambda: os.environ.get(
        "RAFIKI_RECOVER_ADOPT", "1") != "0",
    "RECOVER_PROBE_TIMEOUT_S": lambda: _env_float(
        "RAFIKI_RECOVER_PROBE_TIMEOUT_S", 5.0),
    "RECOVER_RETRY_MAX": lambda: _env_int("RAFIKI_RECOVER_RETRY_MAX", 4),
    "RECOVER_RETRY_BACKOFF_S": lambda: _env_float(
        "RAFIKI_RECOVER_RETRY_BACKOFF_S", 0.2),
    # -- drift closed loop (docs/failure-model.md "Model drift faults").
    # admin/drift.py watches each RUNNING inference job's serving plane
    # for input-distribution shift / confidence decay, launches ONE
    # bounded warm-started retrain, and auto-rolls-out a better candidate
    # through the SLO-judged rollout. Lazy so the NEXT monitor tick picks
    # up a retune:
    #   RAFIKI_DRIFT=1                  enable the closed loop (off by
    #                                   default: monitor, retrain, and
    #                                   rollout all stay dormant)
    #   RAFIKI_DRIFT_INTERVAL_S=2       seconds between monitor ticks
    #   RAFIKI_DRIFT_WINDOW_S=10        trailing sample window the
    #                                   monitor evaluates each tick
    #   RAFIKI_DRIFT_BASELINE_WINDOW_S=10  window frozen as the baseline
    #                                   after enable/rollout (doctor
    #                                   WARNs when shorter than the
    #                                   monitor window)
    #   RAFIKI_DRIFT_MIN_SAMPLES=20    requests needed in a window before
    #                                   a baseline freezes or a verdict
    #                                   counts (idle jobs never flap)
    #   RAFIKI_DRIFT_THRESHOLD=0.5     novelty fraction (share of the
    #                                   current window's digests absent
    #                                   from the baseline population)
    #                                   that counts as distribution shift
    #   RAFIKI_DRIFT_CONF_DROP=0.2     mean top-probability decay vs the
    #                                   baseline that counts as score/
    #                                   confidence drift (probability
    #                                   tasks only)
    #   RAFIKI_DRIFT_SKEW_DELTA=0.4    growth of the single most frequent
    #                                   digest's traffic share vs baseline
    #                                   that counts as skew (one caller
    #                                   dominating a shared door)
    #   RAFIKI_DRIFT_RETRAIN_BUDGET=3  MODEL_TRIAL_COUNT for the
    #                                   auto-retrain (0 = monitor-only:
    #                                   events fire, nothing launches)
    #   RAFIKI_DRIFT_COOLDOWN_S=60     base per-job cooldown after a
    #                                   retrain resolves; doubles per
    #                                   consecutive rollback (capped x16)
    #   RAFIKI_DRIFT_LAUNCH_RETRY_MAX=2  retrain-launch retries (one per
    #                                   tick) before the loop parks with
    #                                   a typed event
    "DRIFT": lambda: os.environ.get("RAFIKI_DRIFT", "0") == "1",
    "DRIFT_INTERVAL_S": lambda: _env_float("RAFIKI_DRIFT_INTERVAL_S", 2.0),
    "DRIFT_WINDOW_S": lambda: _env_float("RAFIKI_DRIFT_WINDOW_S", 10.0),
    "DRIFT_BASELINE_WINDOW_S": lambda: _env_float(
        "RAFIKI_DRIFT_BASELINE_WINDOW_S", 10.0),
    "DRIFT_MIN_SAMPLES": lambda: _env_int("RAFIKI_DRIFT_MIN_SAMPLES", 20),
    "DRIFT_THRESHOLD": lambda: _env_float("RAFIKI_DRIFT_THRESHOLD", 0.5),
    "DRIFT_CONF_DROP": lambda: _env_float("RAFIKI_DRIFT_CONF_DROP", 0.2),
    "DRIFT_SKEW_DELTA": lambda: _env_float("RAFIKI_DRIFT_SKEW_DELTA", 0.4),
    "DRIFT_RETRAIN_BUDGET": lambda: _env_int(
        "RAFIKI_DRIFT_RETRAIN_BUDGET", 3),
    "DRIFT_COOLDOWN_S": lambda: _env_float("RAFIKI_DRIFT_COOLDOWN_S", 60.0),
    "DRIFT_LAUNCH_RETRY_MAX": lambda: _env_int(
        "RAFIKI_DRIFT_LAUNCH_RETRY_MAX", 2),
    # -- control-plane HA (admin/lease.py, admin/standby.py;
    #    docs/failure-model.md "Control-plane HA") --------------------------
    #   RAFIKI_ADMIN_HA=0              leased leadership on boot: the admin
    #                                   acquires the control_lease row (or
    #                                   refuses to start as leader). Off by
    #                                   default: a solo admin needs no lease
    #   RAFIKI_ADMIN_LEASE_TTL_S=10    leadership lease TTL; a leader that
    #                                   cannot renew self-fences at TTL, a
    #                                   standby promotes after it
    #   RAFIKI_ADMIN_LEASE_RENEW_S=0   renewal period (0 = TTL/3)
    #   RAFIKI_ADMIN_LEASE_ACQUIRE_TIMEOUT_S=30  how long a booting leader
    #                                   waits out a predecessor's lease
    #   RAFIKI_ADMIN_ADDRS=            comma list of admin host:port for
    #                                   client failover (leader + standbys)
    #   RAFIKI_ADMIN_FAILOVER_TIMEOUT_S=20  how long Client._call keeps
    #                                   walking the address list before the
    #                                   typed AdminUnavailableError
    #   RAFIKI_ADMIN_STANDBY_POLL_S=0  standby lease-watch period
    #                                   (0 = the renewal period)
    #   RAFIKI_RECOVERY_REPORT_KEEP=5  epoch-suffixed recovery-e<N>.json
    #                                   reports kept per LOGS_DIR
    "ADMIN_HA": lambda: _env_int("RAFIKI_ADMIN_HA", 0),
    "ADMIN_LEASE_TTL_S": lambda: _env_float("RAFIKI_ADMIN_LEASE_TTL_S", 10.0),
    "ADMIN_LEASE_RENEW_S": lambda: _env_float(
        "RAFIKI_ADMIN_LEASE_RENEW_S", 0.0),
    "ADMIN_LEASE_ACQUIRE_TIMEOUT_S": lambda: _env_float(
        "RAFIKI_ADMIN_LEASE_ACQUIRE_TIMEOUT_S", 30.0),
    "ADMIN_ADDRS": lambda: os.environ.get("RAFIKI_ADMIN_ADDRS", ""),
    "ADMIN_FAILOVER_TIMEOUT_S": lambda: _env_float(
        "RAFIKI_ADMIN_FAILOVER_TIMEOUT_S", 20.0),
    "ADMIN_STANDBY_POLL_S": lambda: _env_float(
        "RAFIKI_ADMIN_STANDBY_POLL_S", 0.0),
    "RECOVERY_REPORT_KEEP": lambda: _env_int(
        "RAFIKI_RECOVERY_REPORT_KEEP", 5),
}


def __getattr__(name: str) -> str:
    if name in _DYNAMIC_PATHS:
        return _DYNAMIC_PATHS[name]()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# -- env-knob declaration point (docs/static-analysis.md, FWK101) -----------
# Every RAFIKI_* environment name the package reads MUST appear in this
# file — the framework self-lint (analysis/framework.py) fails tier-1 on
# any read site whose knob is missing here. Knobs config.py itself reads
# above are declared implicitly; these catalogs cover names read at
# their point of use in other modules (lazy/module-local knobs).
#
# ENV_KNOBS are operator-facing: the lint additionally requires each to
# be catalogued in scripts/env.sh and documented under docs/.
ENV_KNOBS = (
    # control-plane / placement
    "RAFIKI_ADMIN_HOST", "RAFIKI_ADMIN_PORT", "RAFIKI_PLACEMENT",
    "RAFIKI_AGENTS", "RAFIKI_AGENT_KEY", "RAFIKI_AGENT_INSECURE",
    "RAFIKI_AGENT_HOST", "RAFIKI_AGENT_PORT", "RAFIKI_AGENT_CHIPS",
    "RAFIKI_LOG_LEVEL",
    # data plane / serving
    "RAFIKI_BROKER", "RAFIKI_SHM_RING_BYTES", "RAFIKI_WIRE_BINARY",
    "RAFIKI_SERVE_INT8",
    # training / JAX backend
    "RAFIKI_COMPILE_CACHE_DIR", "RAFIKI_COMPILE_CACHE_CPU",
    "RAFIKI_COMPILE_CACHE", "RAFIKI_COMPILE_CACHE_MIN_COMPILE_S",
    "RAFIKI_COMPILE_WARM_THRESHOLD_S",
    "RAFIKI_TRAINER_CACHE_CAP", "RAFIKI_SCAN_EPOCH",
    "RAFIKI_SCAN_EPOCH_MAX_BYTES", "RAFIKI_FLASH_THRESHOLD_BYTES",
    "RAFIKI_NATIVE_CACHE", "RAFIKI_VISIBLE_DEVICES",
    "RAFIKI_BACKEND_PROBE_TIMEOUT_S",
    # sandbox
    "RAFIKI_SANDBOX", "RAFIKI_SANDBOX_UID", "RAFIKI_SANDBOX_UID_BASE",
    "RAFIKI_SANDBOX_UID_RANGE", "RAFIKI_SANDBOX_GID",
    "RAFIKI_SANDBOX_KEEP_GID0", "RAFIKI_SANDBOX_MEM_MB",
    "RAFIKI_SANDBOX_NOFILE", "RAFIKI_SANDBOX_NETNS",
    "RAFIKI_SANDBOX_WIDEN_NONOWNED", "RAFIKI_TRIAL_STALL_S",
    # trials / advisor
    "RAFIKI_ADVISOR_RETRY_S", "RAFIKI_TRIAL_VMAP_K_WARN",
    "RAFIKI_INSTALL_DEPS", "RAFIKI_PIP_ARGS",
    # observability
    "RAFIKI_METRICS", "RAFIKI_METRICS_RING_S", "RAFIKI_TRACE_SAMPLE",
    "RAFIKI_TRACE_SLOW_MS", "RAFIKI_TRACE_EXEMPLAR_MAX_MB",
    "RAFIKI_PROFILE",
    # static analysis (this PR)
    "RAFIKI_VERIFY_TEMPLATES",
)

# ENV_INTERNAL are platform plumbing the placement layer writes into
# child-process environments (worker bootstrap contract) — declared so
# the lint knows them, exempt from the operator catalogs.
ENV_INTERNAL = (
    "RAFIKI_SERVICE_ID", "RAFIKI_ADMIN_ADDR", "RAFIKI_CHIP_GRANT",
    "RAFIKI_TRIAL_IDS", "RAFIKI_ORPHAN_SURVIVE",
)

# How long Admin.predict may reuse a resolved app->predictor route without
# re-reading the control-plane DB (serving hot path; see admin.predict).
PREDICT_ROUTE_TTL_S = _env_float("PREDICT_ROUTE_TTL_S", 5.0)

# Request-body ceiling on the dedicated predictor port: one absurd
# Content-Length must not allocate server memory (predictor/server.py
# refuses with 413 before reading).
PREDICT_MAX_BODY_MB = _env_float("PREDICT_MAX_BODY_MB", 64.0)

# Same guard on the admin REST door — higher default because model
# uploads legitimately carry template bytes (base64 in JSON).
ADMIN_MAX_BODY_MB = _env_float("ADMIN_MAX_BODY_MB", 256.0)
