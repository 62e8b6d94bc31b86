#!/usr/bin/env bash
# Single source of deployment config, sourced by every script in this dir.
# The analogue of the reference's .env.sh (reference .env.sh:1-60): secrets,
# host/port, paths, mode — but for a TPU-VM process deployment instead of a
# Docker Swarm one.

export RAFIKI_WORKDIR="${RAFIKI_WORKDIR:-$(pwd)/rafiki_workdir}"
export RAFIKI_DB_PATH="${RAFIKI_DB_PATH:-$RAFIKI_WORKDIR/rafiki.sqlite3}"
# Multi-host control planes: point every host at one PostgreSQL server
# instead of the embedded SQLite file, e.g.
#   export RAFIKI_DB_URL=postgresql://rafiki:pw@dbhost:5432/rafiki
export RAFIKI_ADMIN_HOST="${RAFIKI_ADMIN_HOST:-127.0.0.1}"
export RAFIKI_ADMIN_PORT="${RAFIKI_ADMIN_PORT:-3000}"

# local   = workers as threads inside the admin process (dev; the one
#           process owns every chip)
# process = workers as child processes, each pinned to its chip grant
#           (TPU_VISIBLE_CHIPS) + shm data plane (prod). The admin itself
#           never opens a chip: it counts them in a short-lived child, or
#           takes RAFIKI_VISIBLE_DEVICES=0,1,... as the inventory
export RAFIKI_PLACEMENT="${RAFIKI_PLACEMENT:-process}"

export SUPERADMIN_EMAIL="${SUPERADMIN_EMAIL:-superadmin@rafiki}"
export SUPERADMIN_PASSWORD="${SUPERADMIN_PASSWORD:-rafiki}"
export APP_SECRET="${APP_SECRET:-rafiki-tpu-dev-secret}"

# Optional hardening / serving features (docs/deployment.md):
#   RAFIKI_SANDBOX=1          run untrusted model code in locked-down
#                             children: per-trial uid drop (base/range
#                             RAFIKI_SANDBOX_UID_BASE/_UID_RANGE; 0700
#                             jails), gid drop (RAFIKI_SANDBOX_GID;
#                             KEEP_GID0=1 to retain group root), limits
#                             RAFIKI_SANDBOX_MEM_MB/_NOFILE; optional
#                             RAFIKI_SANDBOX_NETNS=1 network unshare
#                             for CPU-only trials
#   RAFIKI_PREDICTOR_PORTS=1  dedicated POST /predict port per inference
#                             job (bind: RAFIKI_PREDICTOR_HOST)
#   RAFIKI_SERVE_INT8=1       int8 weight-only serving for SDK-trainer
#                             templates — off by default: no cell
#                             measures it on the chip (ROADMAP D3);
#                             doctor WARNs while set
#                             (docs/performance.md)
#   RAFIKI_INSTALL_DEPS=1     provision model dependencies per set into
#                             $RAFIKI_WORKDIR/deps (pip flags via
#                             RAFIKI_PIP_ARGS, e.g. an offline mirror)
#   RAFIKI_AGENTS=h1:p,h2:p   multi-host placement (with
#                             RAFIKI_PLACEMENT=hosts); train AND
#                             inference spread across host agents

# Serving-plane overload control (docs/failure-model.md "Overload
# faults"). Defaults shed instead of queueing unboundedly; 0 disables a cap:
#   RAFIKI_PREDICT_QUEUE_DEPTH=256      per-worker inbox cap; submits past
#                                       it shed 429 + Retry-After
#   RAFIKI_PREDICT_MAX_INFLIGHT=64      per-door in-flight request cap;
#                                       excess sheds 503
#   RAFIKI_PREDICT_HEDGE_SUPPRESS_DEPTH=64  never hedge onto a replica
#                                       whose queue is deeper than this
#   RAFIKI_PREDICT_DRAIN_S=5            predictor stop(): bounded wait for
#                                       in-flight handlers before close

# Prediction result cache + single-flight coalescing (docs/performance.md
# "Prediction caching & single-flight"). Off by default — memoized
# answers are an opt-in behavior change; flushed automatically on
# deploy/rollback/recovery adoption, keyed on served model version,
# excluded for TEXT_GENERATION and ensembled-stochastic jobs:
#   RAFIKI_PREDICT_CACHE=1              answer repeated identical queries
#                                       from a bounded in-process cache
#                                       before any worker queue is touched
#   RAFIKI_PREDICT_CACHE_TTL_S=30       entry lifetime (<=0 disables
#                                       fills; doctor WARNs with cache on)
#   RAFIKI_PREDICT_CACHE_MAX_BYTES=67108864  byte cap, LRU-evicted
#                                       (doctor WARNs past 1 GiB)
#   RAFIKI_PREDICT_SINGLEFLIGHT=1       0 = concurrent identical misses
#                                       each pay their own forward instead
#                                       of sharing the leader's

# Serving wire formats (docs/performance.md "Wire formats"). Internal
# serving hops (shm broker, fleet relay) ride a binary ndarray codec;
# the dedicated predictor port answers binary when clients send
# Accept: application/x-npy. Defaults are right for same-version fleets:
#   RAFIKI_WIRE_BINARY=1            0 = force JSON framing on every
#                                   sender (mixed-version fleet escape
#                                   hatch; receivers always sniff both,
#                                   doctor warns while set)
#   RAFIKI_SHM_RING_BYTES=1048576   shm ring bytes per queue; batched
#                                   binary frames are bigger than
#                                   per-query JSON — size ≳4x the
#                                   largest request body and watch
#                                   ring_used_bytes_hw in serving stats
#                                   (oversized frames shed as typed 413)

# Telemetry plane (docs/observability.md). GET /metrics on all three
# HTTP doors (admin, agent, per-job predictor port) serves Prometheus
# text; cross-hop request tracing is sampled at the predictor door and
# rides queue entries / wire frames / the fleet relay:
#   RAFIKI_METRICS=1                0 = registry writes become no-ops
#                                   (/metrics exposes zeros)
#   RAFIKI_METRICS_RING_S=300       seconds of ~1 s-resolution history in
#                                   the autoscaler ring series (queue
#                                   depth, shed rate, EWMA wait)
#   RAFIKI_TRACE_SAMPLE=0           fraction of predict requests sampled
#                                   into span trees at the predictor door
#                                   (0..1; clients can force one request
#                                   with the X-Rafiki-Trace header)
#   RAFIKI_TRACE_SLOW_MS=0          sampled requests at least this slow
#                                   are appended as JSON-lines exemplars
#                                   to $LOGS_DIR/predict_exemplars.jsonl
#                                   (0 = every sampled request)
#   RAFIKI_TRACE_EXEMPLAR_MAX_MB=64 exemplar file size-rotation cap (one
#                                   .1 generation; doctor WARNs when
#                                   rotation falls behind)

# Elastic serving autoscaler + multi-tenant fair admission
# (docs/failure-model.md "Overload adaptation"). The control loop is OFF
# by default — existing deployments keep their static replica counts:
#   RAFIKI_AUTOSCALE=1                  start the admin-side control loop
#                                       (scale up on sustained shed /
#                                       backlog, down on sustained idle)
#   RAFIKI_AUTOSCALE_INTERVAL_S=2       decision-loop tick interval
#   RAFIKI_AUTOSCALE_WINDOW_S=15        signal window a decision looks at
#   RAFIKI_AUTOSCALE_SHED_THRESHOLD=3   shed events inside the window that
#                                       read "sustained overload"
#   RAFIKI_AUTOSCALE_DEPTH_HIGH=8       mean backlog depth that scales up
#   RAFIKI_AUTOSCALE_DEPTH_LOW=1        max backlog that still counts as
#                                       idle (hysteresis: keep LOW well
#                                       under HIGH; doctor WARNs)
#   RAFIKI_AUTOSCALE_MIN_REPLICAS=1     never drain below this (per job)
#   RAFIKI_AUTOSCALE_MAX_REPLICAS=8     never grow past this
#   RAFIKI_AUTOSCALE_STEP=1             replicas per decision (bounded
#                                       step — the loop cannot stampede)
#   RAFIKI_AUTOSCALE_COOLDOWN_UP_S=5    quiet time before the next up
#   RAFIKI_AUTOSCALE_COOLDOWN_DOWN_S=30 ... before the next down (longer:
#                                       flapping down is worse than
#                                       holding spare capacity a while)
#   RAFIKI_AUTOSCALE_DRAIN_S=10         bounded graceful-drain window per
#                                       removed replica (stop admitting,
#                                       flush its queue, then destroy)
#   RAFIKI_AUTOSCALE_TRAIN_FLOOR=1      chips serving may never borrow
#                                       into — the hard floor that keeps
#                                       training alive through any surge
#   RAFIKI_AUTOSCALE_FAIR=1             per-job weighted fair admission at
#                                       shared doors: a hot job past its
#                                       share 429s, cold jobs keep their
#                                       latency (off by default)
#   RAFIKI_AUTOSCALE_FAIR_WINDOW_S=10   half-life of the per-tenant
#                                       admitted-query charge decay
#   RAFIKI_AUTOSCALE_FAIR_BURST=32      admitted queries a tenant may run
#                                       past its fair share before 429s
#   RAFIKI_AUTOSCALE_FAIR_WEIGHTS=''    "appA=3,appB=1" (unlisted = 1)
# New /metrics series: rafiki_autoscale_{up,down}_total{job},
# rafiki_autoscale_ticks_total, rafiki_autoscale_borrowed_chips,
# rafiki_admission_shed_total{reason="fairness"}, and the ring series
# backlog:job:<id> + shed_rate:job:<id>. Decisions (reason + signal
# snapshot) surface under GET /fleet/health "autoscaler".

# Cold-start resilience (docs/failure-model.md "Cold-start faults",
# sizing recipe in docs/performance.md). Compiled XLA executables
# persist across process death/reschedule/scale-up; workers pre-warm
# their programs BEFORE going routable; the autoscaler can hold warm
# standby replicas so scale-up/replacement is a ~ms promotion:
#   RAFIKI_COMPILE_CACHE=1              0 = never persist compiled
#                                       executables (every boot is cold;
#                                       doctor WARNs while the
#                                       autoscaler/warm pool is on)
#   RAFIKI_COMPILE_CACHE_DIR=...        shared cache dir (default
#                                       <checkout>/xla_cache); where
#                                       JAX_COMPILATION_CACHE_DIR is set
#                                       that directory is used instead,
#                                       as it is
#   RAFIKI_COMPILE_CACHE_CPU=1          opt the CPU backend in (entries
#                                       are machine-feature-tied —
#                                       homogeneous fleets/tests only)
#   RAFIKI_COMPILE_CACHE_MIN_COMPILE_S=0.5  programs compiling faster
#                                       than this are not persisted
#   RAFIKI_COMPILE_WARM_THRESHOLD_S=1.0 boot compile time under this
#                                       still counts warm when cache-hit
#                                       events are unavailable
#   RAFIKI_AUTOSCALE_WARM_POOL=0        K pre-placed pre-warmed standbys
#                                       per hot inference job (0 = off);
#                                       chips ride the arbiter loan book
#                                       and training reclaims drain
#                                       standbys FIRST
#   RAFIKI_AUTOSCALE_WARM_POOL_INTERVAL_S=5  pool top-up/retire tick
#   RAFIKI_AUTOSCALE_WARM_RETRY_MAX=3   failed top-ups per job before
#                                       its pool parks DEGRADED
#   RAFIKI_AUTOSCALE_WARM_RETRY_COOLDOWN_S=30  how long a degraded pool
#                                       waits before retrying
# New /metrics series: rafiki_compile_cache_{hits,misses}_total,
# rafiki_compile_seconds, rafiki_warm_pool_standbys{job},
# rafiki_warm_pool_{promotions,reclaims,ticks}_total. Per-replica warm
# state rides worker stats rows into GET /fleet/health "serving.workers"
# and the predictor /healthz; the pool's report surfaces under
# GET /fleet/health "warm_pool"; doctor's "compile cache" check WARNs on
# the misconfigurations.

# Generative serving — token-streaming TEXT_GENERATION jobs with
# KV-cached decode and continuous batching (docs/serving-generation.md).
# The streaming /generate door lives on the dedicated per-job predictor
# port (RAFIKI_PREDICTOR_PORTS=1); admission charges streams their
# estimated decode footprint (KV blocks when paged, max_tokens under the
# legacy ring), not 1:
#   RAFIKI_GEN_MAX_SLOTS=8              co-resident sequences per
#                                       generation worker — the KV cache
#                                       is preallocated at this width and
#                                       one jitted decode step advances
#                                       them all (doctor WARNs past the
#                                       ~64-slot memory heuristic)
#   RAFIKI_GEN_MAX_TOKENS=64            per-request decode budget cap
#                                       (requests asking more are clamped)
#   RAFIKI_GEN_STREAM_TIMEOUT_S=10      door-side inter-token stall
#                                       timeout: a stream silent this long
#                                       ends with a typed terminal error
#                                       frame, never a hang
#   RAFIKI_GEN_OCCUPANCY_HIGH=0.85      mean occupancy of the binding
#                                       decode resource (KV-pool blocks
#                                       when paged, busy slots otherwise)
#                                       over the autoscaler window that
#                                       reads "saturated" and scales the
#                                       job up (slot_occupancy:job:<id>
#                                       ring; idle needs <= HIGH/2)
# Paged KV + prefix cache + chunked prefill (docs/serving-generation.md
# "Paged KV and prefix caching") — templates advertising the paged decode
# methods serve from a block pool instead of per-slot rings, so resident
# streams are bound by USED tokens, shared prompt prefixes are prefilled
# once, and long-prompt joins never stall resident streams:
#   RAFIKI_GEN_KV_PAGED=1               0 = legacy contiguous ring per
#                                       slot (tier-1's bit-identity
#                                       reference)
#   RAFIKI_GEN_KV_BLOCK_TOKENS=16       K/V rows per pool page — the
#                                       paging granularity (doctor WARNs
#                                       outside 8..2048)
#   RAFIKI_GEN_KV_POOL_BLOCKS=0         pool size in pages; 0 auto-sizes
#                                       to ring parity (slots x
#                                       ceil(max_context/block)); doctor
#                                       WARNs past the chip-memory
#                                       heuristic. Exhaustion preempts
#                                       the YOUNGEST stream (blocks
#                                       freed, request re-queued and
#                                       resumed) — never a crashed round
#   RAFIKI_GEN_PREFIX_CACHE=1           0 = never share prompt-prefix
#                                       blocks (doctor WARNs when the
#                                       shareable-traffic counter shows
#                                       shared prompts anyway)
#   RAFIKI_GEN_PREFILL_CHUNK=64         prompt tokens ingested per
#                                       scheduler round (paged path):
#                                       long-prompt joins interleave
#                                       with decode rounds (0 = one-shot
#                                       prefill)
# Sampling + speculative decoding (docs/serving-generation.md
# "Speculative decoding & sampling") — /generate accepts temperature /
# top_k / top_p / seed with per-token counter-based RNG (streams resume
# bit-identically after preemption; temperature=0 IS greedy), and a
# draft LM trained under a GEN_DRAFT_TRIAL budget proposes k tokens per
# round that the target verifies in ONE fixed-shape forward:
#   RAFIKI_GEN_SAMPLING=1               0 = greedy-only serving: requests
#                                       carrying sampling params answer a
#                                       typed 4xx instead of silently
#                                       decoding greedy
#   RAFIKI_GEN_SPEC=1                   0 = never speculate (plain paged
#                                       decode); 1 = speculate whenever
#                                       the deployed job also carries a
#                                       draft trial and the template
#                                       advertises the verify contract
#   RAFIKI_GEN_SPEC_K=4                 draft tokens proposed per round —
#                                       each round commits 1..k+1 tokens
#                                       in one target forward (doctor
#                                       WARNs outside 1..8)
#   RAFIKI_GEN_SPEC_MIN_RATE=0.3        acceptance-rate floor: doctor
#                                       WARNs when the measured rate sits
#                                       below it (a weak draft makes
#                                       speculation cost throughput);
#                                       faults at the chaos target
#                                       draft/{job}/{service} degrade the
#                                       worker to plain decode, typed +
#                                       permanent, never wrong tokens
# Stream continuity (docs/failure-model.md "Stream continuity") — the
# door journals every stream (prompt, pinned seed, committed tokens) and
# resumes it token-identically on a sibling replica when its worker dies
# or hands it back typed MIGRATING (drain / rollout retirement); a
# resume only ever targets the stream's original model_version:
#   RAFIKI_GEN_RESUME_MAX=3             sibling-resume attempts per
#                                       stream's lifetime; 0 disables
#                                       resume (doctor WARNs with the
#                                       autoscaler on — forced migrations
#                                       then become client errors)
#   RAFIKI_GEN_RESUME_BACKOFF_S=0.05    jittered exponential backoff base
#                                       between attempts (capped by the
#                                       request deadline; a client
#                                       disconnect mid-backoff cancels
#                                       the resume)
#   RAFIKI_GEN_JOURNAL_MAX_KB=64        per-stream journal byte cap
#                                       (~8 B/token): past it the stream
#                                       KEEPS STREAMING but loses resume
#                                       eligibility (doctor WARNs when
#                                       the cap can't hold GEN_MAX_TOKENS)
#   RAFIKI_GEN_JOURNAL_TTL_S=600        journal entry lifetime; an older
#                                       stream is no longer resumable
# New /metrics series: rafiki_gen_ttft_seconds,
# rafiki_gen_door_ttft_seconds, rafiki_gen_intertoken_seconds,
# rafiki_gen_tokens_total, rafiki_gen_slots_busy{service},
# rafiki_gen_evictions_total{reason}, rafiki_gen_kv_blocks_used{service},
# rafiki_gen_kv_pool_blocks{service}, rafiki_gen_prefix_hits_total,
# rafiki_gen_prefix_misses_total, rafiki_gen_prefix_tokens_total,
# rafiki_gen_prefix_evictions_total, rafiki_gen_prefix_shareable_total,
# rafiki_gen_kv_cow_copies_total, rafiki_gen_preemptions_total,
# rafiki_gen_spec_rounds_total, rafiki_gen_spec_proposed_total,
# rafiki_gen_spec_accepted_total, rafiki_gen_spec_degraded_total,
# rafiki_gen_resumes_total{job,reason}, rafiki_gen_journal_bytes{job},
# rafiki_gen_streams_migrated_total.
# Per-job pool footprint, prefix hit rates, speculation acceptance and
# the stream-continuity rollup (resumes by trigger, journal occupancy)
# surface under GET /fleet/health "serving.generation".

# Safe live rollouts (docs/failure-model.md "Rollout faults"). An
# operator (or automation) updates a RUNNING inference job to a new
# trial in place — POST /inference_jobs/<app>/<v>/update — one canary
# replica judged against the incumbents over a trailing window, then a
# rolling replace with graceful drains, with automatic rollback on SLO
# breach / canary crash / deploy failure or timeout (one rollout per
# job; a second update answers typed 409):
#   RAFIKI_ROLLOUT_CANARY_FRACTION=0.1  traffic fraction routed to the
#                                       canary while it is judged
#   RAFIKI_ROLLOUT_JUDGE_WINDOW_S=10    trailing window the SLO judge
#                                       compares canary vs incumbent over
#   RAFIKI_ROLLOUT_MIN_REQUESTS=5       canary samples needed before an
#                                       error-rate/latency verdict (an
#                                       idle job proceeds after 3x the
#                                       window with a low-traffic note)
#   RAFIKI_ROLLOUT_ERR_DELTA=0.1        max (canary - incumbent) error
#                                       rate before automatic rollback
#   RAFIKI_ROLLOUT_P95_FACTOR=3.0       canary ok-latency p95 past
#                                       incumbent p95 x this factor is
#                                       an SLO breach
#   RAFIKI_ROLLOUT_BATCH=1              replicas replaced per rolling
#                                       batch (place new, drain old)
# TEXT_GENERATION jobs roll the same way with stream-granularity version
# lanes: new streams split by the error-diffusion counter, a resumed
# stream only ever targets its original model_version (cross-version
# resume answers typed), and each rolling drain lets resident streams
# run out inside RAFIKI_AUTOSCALE_DRAIN_S before handing the rest back
# MIGRATING for sibling resume.
# New /metrics series: rafiki_rollout_{started,completed,rollbacks}_total
# {job}, rafiki_rollout_requests_total{job,lane,outcome},
# rafiki_rollout_request_seconds{job,lane}. Rollout events (reason +
# signal snapshot) surface under GET /fleet/health "rollouts"; doctor's
# "rollouts" check WARNs on wedged DEPLOYING rows and unacked rollbacks
# (POST .../rollout/ack).

# Drift closed loop (docs/failure-model.md "Model drift faults"). Off by
# default. With RAFIKI_DRIFT=1 the admin watches every RUNNING inference
# job's serving plane (canonical-digest novelty, confidence decay,
# traffic skew vs a frozen post-rollout baseline); a drift verdict
# launches ONE warm-started retrain bounded by the trial budget below,
# and a better-scoring candidate auto-rolls-out through the SLO-judged
# rollout path (canary -> rolling -> done, automatic rollback). Every
# non-success backs the loop off; repeated launch failures park it until
# POST .../drift/ack:
#   RAFIKI_DRIFT=0                      1 = run the closed loop
#   RAFIKI_DRIFT_INTERVAL_S=2.0         monitor tick interval
#   RAFIKI_DRIFT_WINDOW_S=10            trailing window each tick judges
#   RAFIKI_DRIFT_BASELINE_WINDOW_S=10   window sketched into the frozen
#                                       baseline (doctor WARNs if it is
#                                       shorter than the monitor window)
#   RAFIKI_DRIFT_MIN_SAMPLES=20         served samples needed before a
#                                       baseline freezes or a verdict
#                                       fires (idle jobs never trigger)
#   RAFIKI_DRIFT_THRESHOLD=0.5          novelty fraction (window digests
#                                       outside the baseline population)
#                                       that is an input-distribution
#                                       drift verdict
#   RAFIKI_DRIFT_CONF_DROP=0.2          mean top-probability drop below
#                                       the baseline that is a
#                                       confidence-decay verdict
#                                       (probability tasks only)
#   RAFIKI_DRIFT_SKEW_DELTA=0.4         growth of the busiest digest's
#                                       traffic share that is a
#                                       per-tenant skew verdict
#   RAFIKI_DRIFT_RETRAIN_BUDGET=3       MODEL_TRIAL_COUNT of each
#                                       auto-retrain (0 = monitor-only;
#                                       doctor WARNs)
#   RAFIKI_DRIFT_COOLDOWN_S=60          base cooldown after any loop
#                                       outcome; doubled per consecutive
#                                       rollback (cap x16)
#   RAFIKI_DRIFT_LAUNCH_RETRY_MAX=2     retrain-launch retries (one per
#                                       tick) before the loop PARKs
# New /metrics series: rafiki_drift_ticks_total and per-job
# rafiki_drift_{events,retrains,rollouts,rollbacks,parked}_total{job}.
# Loop state surfaces under GET /fleet/health "drift" and per app via
# GET /inference_jobs/<app>/<v>/drift; doctor's "drift loop" check WARNs
# on misconfiguration, parked loops, and rollback flapping.

# Control-plane crash recovery (docs/failure-model.md, "Control-plane
# faults"). A restarted admin reconciles the store against what is
# actually running: adopt surviving workers, reschedule dead-host train
# services, fence orphans. Doors answer 503 + Retry-After while the
# boot reconciliation runs:
#   RAFIKI_RECOVER_ADOPT=1              0 = fence (stop) surviving
#                                       workers instead of adopting them
#                                       on restart (doctor WARNs)
#   RAFIKI_RECOVER_PROBE_TIMEOUT_S=5    per-agent /inventory probe budget
#   RAFIKI_RECOVER_RETRY_MAX=4          metadata-store retries during
#                                       reconcile (jittered backoff)
#   RAFIKI_RECOVER_RETRY_BACKOFF_S=0.2  backoff base for those retries
#   RAFIKI_ADVISOR_RETRY_S=60           worker-side: advisor API calls
#                                       ride out a dead/restarting admin
#                                       this long before erroring the
#                                       executor (0 = fail fast)

# Fleet health (docs/failure-model.md). Safe defaults — tune only for
# failover drills or unusual networks:
#   RAFIKI_AGENT_HEARTBEAT_S=5          /healthz probe interval (0 = off)
#   RAFIKI_AGENT_DOWN_THRESHOLD=3       consecutive misses before DOWN
#   RAFIKI_AGENT_HEARTBEAT_TIMEOUT_S=2  per-probe timeout
#   RAFIKI_AGENT_RETRY_MAX=2            retries for idempotent agent calls
#   RAFIKI_AGENT_RETRY_BACKOFF_S=0.1    backoff base (exponential + jitter)
#   RAFIKI_AGENT_BREAKER_THRESHOLD=3    transport failures to open a circuit
#   RAFIKI_AGENT_BREAKER_COOLDOWN_S=5   fail-fast window before half-open
# Training-plane trial fault tolerance (docs/failure-model.md,
# "Training-plane faults"). Defaults are production-sane:
#   RAFIKI_TRIAL_RETRY_MAX=2            infra-class faults (INFRA/MEM/STALL)
#                                       re-run the SAME trial id this many
#                                       times before it errors; retries never
#                                       consume an extra budget slot (0 = off;
#                                       doctor WARNs)
#   RAFIKI_TRIAL_RETRY_BACKOFF_S=0.5    backoff base between re-runs
#                                       (exponential + full jitter, cap 30 s)
#   RAFIKI_TRIAL_STALL_S=600            sandbox child mute (NO frame at all)
#                                       for this long -> its process group is
#                                       killed and the trial classifies STALL
#                                       (0 = no stall watchdog; raise it for
#                                       templates that legitimately stay
#                                       silent through a long setup)
#   RAFIKI_SANDBOX_WIDEN_NONOWNED=1     0 = a root worker never chmods o+x
#                                       onto ancestor dirs it doesn't own to
#                                       make the repo importable by jailed
#                                       uids (multi-user hosts; pre-grant
#                                       traversal yourself)
#   RAFIKI_TRIAL_QUARANTINE_K=3         user-class faults on near-identical
#                                       knobs before that signature is
#                                       quarantined (proposals re-proposed)
#   RAFIKI_TRIAL_REPROPOSE_MAX=8        bounded re-proposal loop per slot
#   RAFIKI_TRIAL_FAULT_LIMIT=5          consecutive user-class faults that
#                                       error the whole job early with a typed
#                                       reason on the job row (0 = never)
#   RAFIKI_PENDING_FEEDBACK_MAX=256     queued advisor observations awaiting
#                                       retry; beyond it the oldest drop (one
#                                       warning; counted in training stats)
# Vectorized trial execution (docs/performance.md "Vectorized trial
# execution"): templates advertising a PopulationSpec train K advisor
# proposals as ONE vmapped XLA program per chip — the trials/hour/chip
# multiplier no container-per-trial system can reach:
#   RAFIKI_TRIAL_VMAP=1                 0 = kill switch: always scalar
#                                       trials, even for population-
#                                       capable templates
#   RAFIKI_TRIAL_VMAP_K=4               proposals drained per vectorized
#                                       round (per-job override: budget
#                                       TRIAL_VMAP_K; capped by the
#                                       template's max_members, clamped
#                                       by the remaining trial budget)
#   RAFIKI_TRIAL_VMAP_K_WARN=16         doctor's per-chip memory
#                                       heuristic: WARN when K exceeds it
#                                       (K stacked param+opt copies must
#                                       fit HBM beside the dataset)

# Static analysis (docs/static-analysis.md): AST template verifier at
# upload + framework self-lint in tier-1. The lint REQUIRES every
# operator knob to be catalogued in this file (FWK102):
#   RAFIKI_VERIFY_TEMPLATES=enforce     enforce = error findings reject the
#                                       upload with a typed
#                                       ModelVerificationError; warn = accept,
#                                       persist + log findings; off = skip
#                                       (doctor WARNs while jobs are live)

# Knob catalog — names read at their point of use (declared in
# config.py ENV_KNOBS; one line per knob so the self-lint can hold this
# file to completeness):
#   RAFIKI_LOG_LEVEL=INFO               admin/agent process log level
#   RAFIKI_DATA_DIR, RAFIKI_PARAMS_DIR, RAFIKI_LOGS_DIR
#                                       override the $RAFIKI_WORKDIR/{data,
#                                       params,logs} layout per directory
#   RAFIKI_BROKER=shm                   force the shared-memory serving
#                                       data plane (default: auto-detect)
#   RAFIKI_AGENT_HOST / RAFIKI_AGENT_PORT
#                                       bind address of a host agent
#                                       (scripts/start_agent.sh)
#   RAFIKI_AGENT_CHIPS='0,1,2,3'        chip inventory an agent advertises
#   RAFIKI_AGENT_KEY=...                shared fleet key agents require
#                                       (RAFIKI_AGENT_INSECURE=1 runs keyless
#                                       — doctor WARNs)
#   RAFIKI_VISIBLE_DEVICES='0,1'        restrict the JAX device mesh
#   RAFIKI_COMPILE_CACHE_DIR=...        persistent XLA compile cache dir
#                                       (RAFIKI_COMPILE_CACHE_CPU=1 extends
#                                       it to CPU backends — test/dev)
#   RAFIKI_TRAINER_CACHE_CAP=8          compiled-trainer reuse cache entries
#   RAFIKI_SCAN_EPOCH=auto              lax.scan the epoch loop (auto sizes
#                                       via RAFIKI_SCAN_EPOCH_MAX_BYTES)
#   RAFIKI_FLASH_THRESHOLD_BYTES=...    flash-attention engage threshold
#   RAFIKI_NATIVE_CACHE=...             native shm-queue build cache dir
#   RAFIKI_SANDBOX_UID_RANGE=...        uid-hash range for per-trial jails
#                                       (with RAFIKI_SANDBOX_UID_BASE)
#   RAFIKI_SANDBOX_KEEP_GID0=1          jailed children retain group root
#   RAFIKI_SANDBOX_NOFILE=...           RLIMIT_NOFILE inside the jail
#   RAFIKI_BACKEND_PROBE_TIMEOUT_S=75   device count read in a child
#                                       (admin/agent boot, doctor); the
#                                       child is killed at the timeout.
#                                       It takes the chip while it runs
#   RAFIKI_PROFILE=1                    a jax.profiler session around every
#                                       whole trial (train, evaluate,
#                                       persist): a device trace with the
#                                       program's spans on it, under
#                                       LOGS_DIR/profiles/<trial id>. The
#                                       way to a trace from a worker in a
#                                       child process. Costly: off in service

# Control-plane HA (docs/failure-model.md "Control-plane HA"): leased
# leadership + epoch-fenced writes + hot-standby promotion + client
# multi-address failover:
#   RAFIKI_ADMIN_HA=0                   1 = the admin acquires the
#                                       control_lease row on boot (or
#                                       refuses to start as leader);
#                                       default off: a solo admin needs
#                                       no lease and pays no fence
#   RAFIKI_ADMIN_LEASE_TTL_S=10         leadership lease TTL; a leader
#                                       that cannot renew self-fences at
#                                       TTL, a standby promotes after it
#   RAFIKI_ADMIN_LEASE_RENEW_S=0        renewal period (0 = TTL/3; keep
#                                       TTL >= 3x renewals or doctor WARNs)
#   RAFIKI_ADMIN_LEASE_ACQUIRE_TIMEOUT_S=30
#                                       how long a booting leader waits
#                                       out a predecessor's live lease
#   RAFIKI_ADMIN_ADDRS=''               comma list of admin host:port
#                                       (leader + standbys) the client
#                                       SDK walks on refusal/standby-503
#   RAFIKI_ADMIN_FAILOVER_TIMEOUT_S=20  how long Client calls keep
#                                       walking the list before the typed
#                                       AdminUnavailableError
#   RAFIKI_ADMIN_STANDBY_POLL_S=0       standby lease-watch period
#                                       (0 = the renewal period)
#   RAFIKI_RECOVERY_REPORT_KEEP=5       epoch-suffixed recovery-e<N>.json
#                                       reports kept per LOGS_DIR (two
#                                       admins share one across failover)

# Deterministic fault injection — MUST stay off outside drills/tests
# (sites: call_agent, agent, worker — stalls/slows serving replicas for
# overload drills — wire, whose `corrupt` action garbles shm frames for
# codec-corruption drills, db, which fails/delays metadata-store
# statements for control-plane recovery drills, trial, which
# errors/delays/OOMs the trial-run chokepoint for fault-classification
# drills, generate, which injures/stalls one generation slot per
# rule for mid-stream fault drills, deploy, which fails/delays the
# inference-replica placement chokepoint for canary-failure and
# deploy-timeout rollback drills, compile, which delays the warm-up
# chokepoint, corrupts on-disk compile-cache entries (the bit-rot
# drill), or errors a boot for the standby-retry drill, and lease,
# which errors/delays leadership-lease acquisition and renewal at the
# store chokepoint for false-lease-loss, slow-renewal-near-TTL and
# self-fence drills):
#   RAFIKI_CHAOS=''                     e.g. 'site=agent;action=drop;times=3'
export RAFIKI_CHAOS="${RAFIKI_CHAOS:-}"

# Persistent XLA compile cache shared across trials/restarts
# (replaces the reference's per-boot `pip install` warmup cost,
# reference scripts/start_worker.py:6-9): placed from outside with
# JAX_COMPILATION_CACHE_DIR (used as it is, inherited by every worker),
# else RAFIKI_COMPILE_CACHE_DIR, else <checkout>/xla_cache — a fixed
# path, never under the workdir (sdk/compile_cache.py). Nothing is
# exported here on purpose.

RAFIKI_PID_FILE="$RAFIKI_WORKDIR/admin.pid"
RAFIKI_ADMIN_LOG="$RAFIKI_WORKDIR/logs/admin.log"
