"""Deterministic fault injection for the fleet control/data planes.

Every failover path in the fleet health subsystem (placement/hosts.py
heartbeats, utils/agent_http.py circuit breaker, cache/fleet.py eviction)
must be exercisable by fast CPU-only tier-1 tests without real hosts
dying. This module is the single switchboard: the two wire-protocol
chokepoints — ``call_agent`` (client side) and the agent HTTP server
(placement/agent.py) — ask it before each request, and it answers with an
injected fault (or nothing) on a **deterministic schedule** driven by
per-rule hit counters, never randomness.

Rules come from the ``RAFIKI_CHAOS`` environment variable (off by
default — empty/unset means every hook is a no-op) or programmatically
via :func:`install` in tests. Env format: ``|``-separated rules of
``;``-separated ``key=value`` fields, e.g. ::

    RAFIKI_CHAOS='site=agent;action=error;code=503;match=/predict_relay;times=2'
    RAFIKI_CHAOS='site=call_agent;action=drop;match=9001|site=agent;action=delay;delay_s=0.2'

Fields:

    site     where to inject: ``call_agent`` (admin-side transport),
             ``agent`` (host agent server), ``worker`` (inference
             serve loop — overload drills: slow/stalled replicas),
             ``wire`` (shm frames popped off the serving rings, before
             decode — corruption drills), ``db`` (metadata-store
             statements — transient store-failure drills for
             control-plane recovery), ``trial`` (the trial-run
             chokepoint in the train worker — fault-classification drills),
             ``cache`` (the prediction result cache's lookup/fill/join
             operations — degraded-cache drills: a broken cache must
             degrade to miss-path serving, never fail a request),
             ``generate`` (the generation decode loop — mid-stream
             fault / stalled-decode drills, one ask per active slot per
             round), ``deploy`` (the inference-replica placement
             chokepoint — canary-failure / deploy-timeout rollback
             drills for live rollouts), or ``drift`` (the drift loop's
             monitor-tick and retrain-launch chokepoints — degraded-
             monitor / parked-launch drills), or ``compile`` (the worker
             warm-up / compile chokepoint — cold-start drills: slow
             compiles, corrupt cache entries, failed standby warm-ups),
             or ``lease`` (the control-plane leadership-lease
             acquire/renew chokepoint — false-lease-loss, slow-renewal
             and self-fence drills for admin HA).
             Required.
    action   ``drop`` (connection-level failure; at site=worker the batch
             is silently swallowed — a stalled replica), ``delay`` (sleep
             ``delay_s`` then proceed — a slow replica), ``error``
             (HTTP ``code``; at site=worker the batch fails; at
             site=trial a typed transient INFRA fault), ``corrupt``
             (site=wire: truncate/garble the raw frame bytes;
             site=compile: garble the persistent compile-cache entries),
             or ``oom`` (site=trial only: raise MemoryError — the
             MEM-class drill). Required.
    match    substring filter on the target ("addr path" client-side,
             request path server-side). Empty matches everything.
    after    skip the first N matching requests (default 0).
    times    inject into at most N matching requests (default: no cap) —
             ``after``/``times`` windows let a test kill a host "mid-
             serving" at an exact request ordinal.
    every    of the post-``after`` matches, inject into every k-th
             (default 1 = all).
    delay_s  sleep for ``delay`` (default 0.05).
    code     HTTP status for ``error`` (default 503).

The controller re-parses ``RAFIKI_CHAOS`` whenever the env value changes
(counters reset with it), so monkeypatched tests and spawned agent
subprocesses both pick rules up without plumbing.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional

logger = logging.getLogger(__name__)

ENV_VAR = "RAFIKI_CHAOS"

SITE_CALL_AGENT = "call_agent"
SITE_AGENT = "agent"
# inference worker serve loop (worker/inference.py): the overload-drill
# site. `delay` makes a worker slow (queues back up behind a live model —
# the condition that triggers admission shed + hedge suppression), `drop`
# makes it silently swallow a batch (futures never resolve; the
# predictor's SLO machinery takes over), `error` fails the batch.
# GENERATION replicas (worker/generation.py) ask this site once per
# serve-loop round with target "{job_id}/{service_id}" — the kill-replica
# chaos target: `drop` is the SIGKILL drill (the loop exits ABRUPTLY,
# resident streams abandoned un-handed-back; the door's journal resumes
# them on siblings when the dead replica's queue vanishes), `error` is a
# clean kill (typed MIGRATING handoff of every resident stream first),
# `delay` stalls the whole replica for a round.
SITE_WORKER = "worker"
# serving wire chokepoint (cache/shm_broker.py): frames popped off the
# shm rings, BEFORE decode. `corrupt` garbles/truncates the raw bytes on
# a deterministic schedule — the drill that proves a corrupt frame
# yields a typed per-request error (WireFormatError -> skip -> the
# request's SLO timeout), never a worker-loop crash. Target string is
# the shm queue name, so `match` can pick the query vs response ring.
SITE_WIRE = "wire"
# metadata store (db/database.py): every statement the DAL issues asks
# this site first; target string is the SQL text, so `match` can pick a
# table ("FROM service") or verb ("UPDATE"). `error` raises a typed
# transient store failure, `delay` models a slow/contended store — the
# drill that proves control-plane recovery retries with bounded jittered
# backoff instead of aborting reconciliation (docs/failure-model.md).
SITE_DB = "db"
# generation decode loop (worker/generation.py): one ask per ACTIVE SLOT
# per decode round, target "{job_id}/{service_id}/slot{i}/{seq_id}" so
# `match` can injure one co-resident sequence mid-stream. `error` fails
# exactly that sequence (typed terminal error frame on its stream;
# siblings keep decoding), `drop` mutes the slot's deltas — the stalled-
# decode drill the door's inter-token timeout must convert into a typed
# error frame, never a silent hang — and `delay` slows the whole step
# (a slow decode) — docs/serving-generation.md "Chaos drills".
# A second target shape lives at this site: "draft/{job_id}/{service_id}"
# is asked once per speculative round BEFORE the draft proposes. `delay`
# slows the round, `drop` skips speculation for that round (plain
# decode), `error` permanently degrades the worker to plain decode with
# a typed reason (gen_spec_degraded) — the crashing/stalling-draft drill:
# a broken draft model must cost throughput, never correctness.
SITE_GENERATE = "generate"
# inference-replica placement chokepoint (admin/services.py — the
# shared _chaos_deploy ask inside create_inference_services,
# _scale_up_one, and the rollout controller's deploy_version_replica):
# one ask per replica placement, target "{inference_job_id}/{trial_id}".
# `error` (or `drop`) fails the placement with a typed
# ServiceDeploymentError — the deterministic canary-failure drill —
# and `delay` models a slow deploy (stacked against the rollout's
# deploy deadline, it becomes the deploy-timeout rollback drill) —
# docs/failure-model.md "Rollout faults".
SITE_DEPLOY = "deploy"
# prediction result cache (predictor/result_cache.py): one ask per
# lookup / fill / single-flight join, target "{inference_job_id}/{op}"
# (op in lookup|fill|join) so `match` can injure one operation class.
# `error` raises inside the cache call — the drill that proves a broken
# cache DEGRADES to miss-path serving (the predictor absorbs it, the
# request is answered by a real forward, never failed); `delay` models
# a slow cache. docs/failure-model.md "Cache faults".
SITE_CACHE = "cache"
# drift closed loop (admin/drift.py): two chokepoints, target
# "tick/{inference_job_id}" (the monitor's per-job evaluation) and
# "launch/{inference_job_id}" (the bounded-retrain launch). `error` at
# tick proves the degradation contract — a broken monitor is absorbed
# and never touches serving; `error` at launch drives the bounded
# launch retries and the PARKED terminal state; `delay` models a slow
# monitor/launch — docs/failure-model.md "Model drift faults".
SITE_DRIFT = "drift"
# trial-run chokepoint (worker/train.py _execute_trial): one ask per
# trial ATTEMPT, target "{sub_train_job_id} {trial_id}". `error` raises
# a typed transient fault the classification classifies INFRA (the
# bounded-retry drill: the trial re-runs under the same id without
# burning a budget slot), `oom` raises MemoryError (classified MEM),
# `delay` models a slow trial start — docs/failure-model.md
# "Training-plane faults".
SITE_TRIAL = "trial"
# worker warm-up / compile chokepoint (worker/warmup.py run_warmup):
# one ask per warm-up program, target
# "{inference_job_id}/{service_id}/{program}". `delay` models a slow
# compile (the still-warming replica stays DEPLOYING — the drill that
# proves the predictor never routes to it), `error` raises the typed
# WarmupError that fails the worker's startup (the bounded standby-
# retry drill), and `corrupt` garbles the persistent compile-cache
# entries on disk first (the bit-rot drill: JAX's reader absorbs the
# damage and the boot degrades to a fresh compile, never a crash) —
# docs/failure-model.md "Cold-start faults".
SITE_COMPILE = "compile"
# control-plane leadership lease (db/database.py acquire_lease /
# renew_lease): one ask per lease operation, target "acquire" or
# "renew". `error` is the false-lease-loss drill (a renewal that errors
# must NOT drop leadership — the TTL clock decides; a leader that cannot
# renew within the TTL self-fences its writes BEFORE the standby can
# acquire), `delay` models a slow/contended store near the TTL edge
# (renewal landing late, promotion racing expiry) —
# docs/failure-model.md "Control-plane HA".
SITE_LEASE = "lease"

ACTION_DROP = "drop"
ACTION_DELAY = "delay"
ACTION_ERROR = "error"
ACTION_CORRUPT = "corrupt"
ACTION_OOM = "oom"


class ChaosSpecError(ValueError):
    """RAFIKI_CHAOS could not be parsed; raised at install, logged (once
    per bad value) when coming from the environment."""


@dataclass
class ChaosRule:
    site: str
    action: str
    match: str = ""
    after: int = 0
    times: Optional[int] = None
    every: int = 1
    delay_s: float = 0.05
    code: int = 503
    hits: int = field(default=0, compare=False)  # matching requests seen

    def __post_init__(self) -> None:
        if self.site not in (SITE_CALL_AGENT, SITE_AGENT, SITE_WORKER,
                             SITE_WIRE, SITE_DB, SITE_TRIAL,
                             SITE_GENERATE, SITE_DEPLOY, SITE_CACHE,
                             SITE_DRIFT, SITE_COMPILE, SITE_LEASE):
            raise ChaosSpecError(f"unknown chaos site {self.site!r}")
        if self.action not in (ACTION_DROP, ACTION_DELAY, ACTION_ERROR,
                               ACTION_CORRUPT, ACTION_OOM):
            raise ChaosSpecError(f"unknown chaos action {self.action!r}")
        if self.action == ACTION_CORRUPT and self.site not in (SITE_WIRE,
                                                               SITE_COMPILE):
            raise ChaosSpecError(
                "chaos action 'corrupt' only applies at site=wire (raw "
                "frame bytes) or site=compile (cache entries on disk)")
        if self.action == ACTION_OOM and self.site != SITE_TRIAL:
            raise ChaosSpecError(
                "chaos action 'oom' only applies at site=trial "
                "(trial-run chokepoint)")
        if self.every < 1:
            raise ChaosSpecError("chaos 'every' must be >= 1")

    def fires(self, site: str, target: str) -> bool:
        """Count a request against this rule; True when the fault applies.
        Deterministic: depends only on the request order seen so far."""
        if site != self.site or self.match not in target:
            return False
        self.hits += 1
        n = self.hits - self.after  # 1-based index past the warm-up window
        if n <= 0:
            return False
        if self.times is not None and n > self.times * self.every:
            return False
        return (n - 1) % self.every == 0


def parse_rules(spec: str) -> List[ChaosRule]:
    rules: List[ChaosRule] = []
    for chunk in spec.split("|"):
        chunk = chunk.strip()
        if not chunk:
            continue
        fields = {}
        for kv in chunk.split(";"):
            kv = kv.strip()
            if not kv:
                continue
            if "=" not in kv:
                raise ChaosSpecError(f"chaos field {kv!r} is not key=value")
            k, v = kv.split("=", 1)
            fields[k.strip()] = v.strip()
        unknown = set(fields) - {"site", "action", "match", "after",
                                 "times", "every", "delay_s", "code"}
        if unknown:
            raise ChaosSpecError(f"unknown chaos fields {sorted(unknown)}")
        try:
            rules.append(ChaosRule(
                site=fields.get("site", ""),
                action=fields.get("action", ""),
                match=fields.get("match", ""),
                after=int(fields.get("after", 0)),
                times=(int(fields["times"]) if "times" in fields else None),
                every=int(fields.get("every", 1)),
                delay_s=float(fields.get("delay_s", 0.05)),
                code=int(fields.get("code", 503)),
            ))
        except (TypeError, ValueError) as e:
            if isinstance(e, ChaosSpecError):
                raise
            raise ChaosSpecError(f"bad chaos rule {chunk!r}: {e}") from e
    return rules


class ChaosController:
    """Holds the active rule set; thread-safe (agent server handlers and
    the admin's sender/heartbeat threads all consult it concurrently)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._rules: List[ChaosRule] = []
        self._installed = False      # programmatic rules win over env
        self._env_value: Optional[str] = None
        self._env_bad: Optional[str] = None

    def install(self, rules: List[ChaosRule]) -> None:
        with self._lock:
            self._rules = list(rules)
            self._installed = True

    def clear(self) -> None:
        with self._lock:
            self._rules = []
            self._installed = False
            self._env_value = None
            self._env_bad = None

    def enabled(self) -> bool:
        with self._lock:
            self._refresh_env_locked()
            return bool(self._rules)

    def hit(self, site: str, target: str) -> Optional[ChaosRule]:
        """Record one request at ``site`` against every rule; return the
        first rule whose schedule fires, else None.

        Fast path without the lock when chaos is provably inactive (no
        installed rules, no rules loaded, env unset): every metadata-store
        statement and every popped shm frame asks this function — they
        must not all contend on one process-global mutex to learn that
        nothing is injected. The unlocked reads are benign: a racing
        install/env-set is picked up by the next call."""
        # lint: unguarded(documented lock-free fast path; a racing install/env-set is picked up by the next call)
        if (not self._installed and not self._rules and not self._env_value
                and not os.environ.get(ENV_VAR)):
            # (a truthy cached _env_value means the env was JUST unset:
            # fall through once so the locked refresh resets the cache)
            return None
        with self._lock:
            self._refresh_env_locked()
            for rule in self._rules:
                if rule.fires(site, target):
                    logger.warning("chaos %s@%s -> %s", site, target,
                                   rule.action)
                    return rule
        return None

    def _refresh_env_locked(self) -> None:  # guarded-by: _lock
        if self._installed:
            return
        value = os.environ.get(ENV_VAR, "")
        if value == self._env_value:
            return
        self._env_value = value
        try:
            self._rules = parse_rules(value)
            self._env_bad = None
        except ChaosSpecError as e:
            self._rules = []
            if value != self._env_bad:
                self._env_bad = value
                logger.error("ignoring unparseable %s: %s", ENV_VAR, e)


_controller = ChaosController()

install = _controller.install
clear = _controller.clear
enabled = _controller.enabled
hit = _controller.hit


def sleep_for(rule: ChaosRule) -> None:
    """Apply a delay rule (kept here so call sites stay one-liners)."""
    time.sleep(rule.delay_s)


def corrupt_bytes(raw: bytes, rule: ChaosRule) -> bytes:
    """Apply a site=wire `corrupt` rule to popped frame bytes.
    Deterministic in the rule's hit count: odd hits truncate (a partial
    write), even hits garble bytes in place (bit rot) — both classes of
    damage a decoder must survive."""
    if not raw:
        return raw
    if rule.hits % 2:
        return raw[: max(len(raw) // 2, 1)]
    buf = bytearray(raw)
    for i in range(0, len(buf), max(len(buf) // 8, 1)):
        buf[i] ^= 0xA5
    return bytes(buf)
