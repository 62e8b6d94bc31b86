"""Best-effort OS sandbox for untrusted model-template code.

The reference got isolation for free: every trial executor was a Docker
container with only its own volume mounts
(/root/reference/dockerfiles/worker.Dockerfile:1-31,
rafiki/container/docker_swarm.py:128-148). A process-native TPU stack
needs its own story — SURVEY.md §7 lists it as a hard part. This module
runs the untrusted slice of a trial (model import, train, evaluate,
dump_parameters) in a restricted CHILD process; everything trusted —
store access, advisor coordination, params persistence, budget
accounting — stays in the worker (worker/train.py), which talks to the
child over a line-framed pipe protocol.

Threat model (documented, not absolute):

- PROTECTED against an uploaded template that tries to (a) read OR
  write other trials' jails (params, mid-trial checkpoints — each jail
  is 0700 and owned by its own per-trial uid), (b) read/modify the
  metadata store (SQLite file), (c) see admin credentials / agent keys /
  store paths in its environment, (d) exhaust fds or address space,
  (e) scribble outside its jail cwd via relative paths, or (f) read
  group-root files (0640 root:root) — the credential drop clears
  supplementary groups and drops gid too (``os.setgroups([])`` +
  ``setgid``), unlike r4's gid-0-retained design.
  Mechanisms: scrubbed environment (allowlist), cwd jailed to a
  per-trial directory, RLIMIT_NOFILE/RLIMIT_AS/RLIMIT_CORE,
  PR_SET_NO_NEW_PRIVS, and — when the worker runs as root (the TPU-VM
  deployment default) — a drop to a PER-TRIAL uid (hashed from the jail
  name into [RAFIKI_SANDBOX_UID_BASE, +RAFIKI_SANDBOX_UID_RANGE); set
  RAFIKI_SANDBOX_UID_RANGE=0 for the r4-style single
  ``RAFIKI_SANDBOX_UID``) and to gid ``RAFIKI_SANDBOX_GID`` (default
  65534; ``RAFIKI_SANDBOX_KEEP_GID0=1`` restores gid 0 for deployments
  whose TPU device nodes are group-0 gated). Owner-only files (params
  dir 0700, DB 0600 — enforced by db/database.py and worker/train.py)
  and sibling jails are unreadable; world-readable code (repo, venv,
  stdlib) still imports — the grants the parent makes to ensure that
  (directory-traversal bits along the repo/dataset paths) are logged.
- NOT protected BY DEFAULT: network access — the child shares the host
  network namespace (trials may need sockets), so a
  hostile template can dial loopback control-plane ports (which is why
  the admin REST requires JWTs and agents require keys even from
  localhost). ``RAFIKI_SANDBOX_NETNS=1`` closes this for CPU-only
  trials by unsharing the network namespace (child keeps a down
  loopback, no reachability at all). Also not bounded: CPU time
  (trials legitimately train for hours; TRIAL_TIMEOUT_S covers
  runaways via the stop protocol). Uid-drop isolation is unavailable
  when the worker itself runs unprivileged — then only the env scrub +
  cwd jail + rlimits apply. Full containment still calls for VMs/gVisor
  at the fleet boundary.

Protocol (child = python -m rafiki_tpu.sdk.sandbox_child):

- parent -> child stdin: one setup JSON line, then optionally ``STOP\\n``
  (the mid-trial stop verdict — TRIAL_TIMEOUT_S / TIME_HOURS / ASHA);
- child -> parent stdout, one JSON frame per line:
    {"t": "log",  "line": <ModelLogger serialized record>}
    {"t": "done", "score": float, "params_b64": str}
    {"t": "err",  "error": str, "traceback": str}
  METRICS log frames double as the parent's stop-check decision points,
  exactly like the in-process logger wiring they replace.

Serving runs under the same flag: inference workers host the uploaded
template in a persistent serve-mode child (``SandboxedModelServer``) that
answers one predict frame per batch — the trusted worker keeps the params
file, store, and data plane (worker/inference.py).

Enable with ``RAFIKI_SANDBOX=1`` (worker/train.py checks per trial).
The mode is CPU-ONLY for now: the jailed child runs with
``JAX_PLATFORMS=cpu``, because its parent worker already holds the chip
(see ``_child_env``).
"""

from __future__ import annotations

import base64
import json
import logging
import os
import signal
import stat
import subprocess
import sys
import threading
from typing import Any, Callable, Dict, Optional, Tuple

logger = logging.getLogger(__name__)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# env vars the child KEEPS (everything else is scrubbed). Compute needs
# the JAX/XLA/TPU configuration; PATH/TMP for the interpreter.
ENV_ALLOWLIST_PREFIXES = (
    "JAX_", "XLA_", "TPU_", "LIBTPU_", "PJRT_",
    "PYTHON", "LC_", "LANG",
)
ENV_ALLOWLIST = ("PATH", "TMPDIR", "TZ", "RAFIKI_CHIP_GRANT",
                 "RAFIKI_COMPILE_CACHE_DIR",
                 # serving-numerics switch (sdk/quant.py) — config, not a
                 # secret; the sandboxed trainer must see the same value
                 # the in-process path would
                 "RAFIKI_SERVE_INT8")


class SandboxError(Exception):
    """The sandboxed trial failed (model error, limit hit, or protocol
    breakdown); carries the child-side traceback when there is one.

    Subclasses carry a ``kind`` from the trial fault classification
    (worker/faults.py — plain strings here so the sdk layer stays
    import-free of the worker layer): the worker's retry/quarantine
    machinery branches on it instead of parsing messages."""

    kind = "INFRA"


class SandboxInfraError(SandboxError):
    """The platform failed the child: spawn failure, protocol breakdown,
    killed by an unexplained signal. Retryable (same trial id)."""

    kind = "INFRA"


class SandboxMemError(SandboxError):
    """The child breached its memory envelope: RLIMIT_AS MemoryError
    from model code, or SIGKILL while RAFIKI_SANDBOX_MEM_MB was
    active (kernel/OOM enforcement)."""

    kind = "MEM"


class SandboxUserError(SandboxError):
    """Model code raised (an ``err`` frame from sandbox_child with
    where=model). Terminal: the knobs are infeasible, not the infra."""

    kind = "USER"


class SandboxStallError(SandboxError):
    """The child went mute before its first frame for
    RAFIKI_TRIAL_STALL_S and the no-frame watchdog killed its process
    group (wedged import, a backend that never comes up). Retryable."""

    kind = "STALL"


class SandboxTimeoutError(SandboxError):
    """The trial blew through its TRIAL_TIMEOUT_S budget and ignored
    the STOP verdict (a mute runaway); the watchdog terminated it."""

    kind = "TIMEOUT"


def stall_deadline_s() -> float:
    """RAFIKI_TRIAL_STALL_S: how long a sandbox child may produce NO
    frame at all before the watchdog kills it (0 disables). Armed only
    until the first frame — once the template has spoken, mid-training
    silence is legitimate (an epoch can take longer than any sane stall
    deadline) and TRIAL_TIMEOUT_S owns runaways."""
    return float(os.environ.get("RAFIKI_TRIAL_STALL_S", "600"))


def sandbox_enabled() -> bool:
    return os.environ.get("RAFIKI_SANDBOX") == "1"


def sandbox_uid() -> Optional[int]:
    """The fixed fallback uid (RAFIKI_SANDBOX_UID_RANGE=0 mode), or None
    when the worker is unprivileged (no drop possible — the remaining
    layers still apply). Per-jail uids come from :func:`uid_for_jail`."""
    if os.geteuid() != 0:
        return None
    return int(os.environ.get("RAFIKI_SANDBOX_UID", "65534"))


def _uid_range() -> Tuple[int, int]:
    base = int(os.environ.get("RAFIKI_SANDBOX_UID_BASE", "210000"))
    rng = int(os.environ.get("RAFIKI_SANDBOX_UID_RANGE", "4096"))
    return base, rng


def _hashed_uid(ident: str, base: int, rng: int) -> int:
    """THE uid-hash: make_jail's collision probe reserves this value for
    still-root-owned sibling jails, so it must stay byte-identical with
    what uid_for_jail computes — one copy only."""
    import zlib

    return base + (zlib.crc32(ident.encode()) % rng)


def uid_for_jail(jail_dir: str) -> Optional[int]:
    """Uid the child in this jail drops to. STICKY: once make_jail has
    chowned the jail, its owner IS the answer (so a resumed trial maps
    to the uid that wrote its mid-trial checkpoint even across a
    base/range reconfiguration — and collision probing stays stable).
    For a jail that doesn't exist yet, the basename (trial id / serve
    id) hashes into [RAFIKI_SANDBOX_UID_BASE, +RAFIKI_SANDBOX_UID_RANGE)
    — make_jail then probes that choice against live sibling jails.
    Distinct uids + 0700 jails are what isolate concurrent trials from
    EACH OTHER (advisor r4 finding: a shared uid let one trial corrupt a
    sibling's checkpoint). Range 0 restores the single shared
    RAFIKI_SANDBOX_UID. None when the worker is unprivileged."""
    if os.geteuid() != 0:
        return None
    base, rng = _uid_range()
    if rng <= 0:
        return sandbox_uid()
    try:
        owner = os.stat(jail_dir).st_uid
        if base <= owner < base + rng:
            return owner
    except OSError:
        pass
    return _hashed_uid(os.path.basename(os.path.abspath(jail_dir)),
                       base, rng)


def sandbox_gid() -> int:
    """Gid the child drops to. Default 65534 (nogroup); gid 0 only via
    the explicit RAFIKI_SANDBOX_KEEP_GID0=1 escape hatch (TPU device
    nodes gated on group 0 in some deployments)."""
    if os.environ.get("RAFIKI_SANDBOX_KEEP_GID0") == "1":
        return 0
    return int(os.environ.get("RAFIKI_SANDBOX_GID", "65534"))


def _child_env(jail_dir: str) -> Dict[str, str]:
    env = {
        k: v for k, v in os.environ.items()
        if k in ENV_ALLOWLIST or k.startswith(ENV_ALLOWLIST_PREFIXES)
    }
    env["HOME"] = jail_dir
    env["TMPDIR"] = jail_dir
    env["PYTHONPATH"] = _REPO_ROOT
    # CPU-only for now: the worker that spawns this child has already
    # initialised its JAX backend (compile cache, device grant) and so
    # holds the chip — a chip belongs to one process, and a child that
    # reached for it would fail or hang
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _ensure_traversal(path: str, read: bool = False) -> None:
    """Give the dropped child directory-traversal (execute) bits on
    ``path`` and every ancestor this process may widen — group AND
    other x, since the child may run with gid 0 (KEEP_GID0 mode) or an
    anonymous gid. ``read=True`` additionally grants read on ``path``
    itself (package roots need listing for import; ancestors never do).

    An unprivileged worker never touches files it doesn't own; a ROOT
    worker (the only case where uid drops — and therefore traversal
    grants — matter at all) additionally widens non-owned directories,
    but with the *execute bit only*, never read: a repo checkout under
    e.g. a /root whose directory is owned by some provisioning uid
    would otherwise make EVERY sandboxed trial fail at import with a
    spawn-class fault, while an x-only grant exposes nothing listable —
    reaching a file still requires knowing its path and passing its own
    mode bits. On a multi-user host where even that is unacceptable
    (an o+x'd home directory persists after the worker exits),
    ``RAFIKI_SANDBOX_WIDEN_NONOWNED=0`` restores the strict owner-only
    rule — the operator then pre-grants traversal along the repo path
    themselves. Every widening is LOGGED (advisor r4: these are
    system-visible side effects — e.g. /root gains o+x so the jailed
    uid can reach /root/repo — and operators must be able to see
    them)."""
    travers = stat.S_IXGRP | stat.S_IXOTH
    p = os.path.abspath(path)
    want = travers | (stat.S_IRGRP | stat.S_IROTH if read else 0)
    is_root = (os.geteuid() == 0 and os.environ.get(
        "RAFIKI_SANDBOX_WIDEN_NONOWNED", "1") != "0")
    while True:
        try:
            st = os.stat(p)
            owned = st.st_uid == os.getuid()
            # non-owned dirs (root only): traversal x, never read bits
            eff = want if owned else (want & travers if is_root else 0)
            if eff and (st.st_mode & eff) != eff:
                os.chmod(p, st.st_mode | eff)
                logger.info(
                    "sandbox: widened %s %o -> %o (traversal grant for "
                    "jailed uids)", p, stat.S_IMODE(st.st_mode),
                    stat.S_IMODE(st.st_mode | eff))
        except OSError:
            pass
        parent = os.path.dirname(p)
        if parent == p:
            return
        p = parent
        want = travers  # ancestors get x only, never read


def grant_dataset_access(uri: str) -> None:
    """Local-file dataset URIs must be readable by the jailed uid: add
    group+other read on the file and traversal on its ancestors (no-ops
    for http(s) URIs and files we don't own)."""
    path = uri[7:] if uri.startswith("file://") else uri
    if not os.path.isabs(path) or not os.path.exists(path):
        return
    _ensure_traversal(os.path.dirname(path))
    try:
        st = os.stat(path)
        want = stat.S_IRGRP | stat.S_IROTH
        if st.st_uid == os.getuid() and (st.st_mode & want) != want:
            os.chmod(path, st.st_mode | want)
            logger.info("sandbox: widened dataset %s %o -> %o", path,
                        stat.S_IMODE(st.st_mode),
                        stat.S_IMODE(st.st_mode | want))
    except OSError:
        pass


def jail_path(base_dir: str, trial_id: str) -> str:
    """THE definition of where a trial's jail lives — cleanup code
    (worker/train.py _cleanup_ckpt) resolves through this too."""
    return os.path.join(base_dir, "jail", trial_id)


def make_jail(base_dir: str, trial_id: str) -> str:
    """Per-trial jail cwd: 0700 and owned by THIS trial's uid (when the
    worker is root), so sibling trials — distinct uids, no shared
    group — can neither read nor corrupt its mid-trial checkpoints.
    Stable across worker restarts (an existing jail keeps its owner uid,
    see uid_for_jail) so checkpoints resume; a fresh jail's hashed uid
    is linear-probed against every sibling jail's owner so two LIVE
    trials can never silently share a uid (review r5: crc32 % 4096
    collides with ~50% odds by ~75 jails)."""
    jail = jail_path(base_dir, trial_id)
    existed = os.path.isdir(jail)
    os.makedirs(jail, exist_ok=True)
    uid = uid_for_jail(jail)
    if uid is not None:
        base, rng = _uid_range()
        sticky = False
        if existed and rng > 0:
            try:
                owner = os.stat(jail).st_uid
                sticky = base <= owner < base + rng
            except OSError:
                pass
        if rng > 0 and not sticky:
            # Serialize (probe + chown) across worker processes sharing
            # this WORKDIR: without the flock, two jails hashing to the
            # same uid could both probe before either chown lands and
            # silently share a uid (review r5 TOCTOU). A sibling that is
            # still root-owned inside the lock is a creator WAITING on
            # this lock — reserve the uid its name hashes to.
            import fcntl

            parent = os.path.dirname(jail)
            # 0600 — the lock lives in a tree jailed children can
            # traverse, and flock works on a read-only fd: a hostile
            # template holding it would wedge all future jail creation
            lock_fd = os.open(os.path.join(parent, ".uidlock"),
                              os.O_WRONLY | os.O_CREAT, 0o600)
            lockf = os.fdopen(lock_fd, "w")
            try:
                os.fchmod(lock_fd, 0o600)  # pre-existing wider file
                fcntl.flock(lockf, fcntl.LOCK_EX)
                taken = set()
                for name in os.listdir(parent):
                    p = os.path.join(parent, name)
                    if p == jail or not os.path.isdir(p):
                        continue
                    try:
                        owner = os.stat(p).st_uid
                    except OSError:
                        continue
                    if base <= owner < base + rng:
                        taken.add(owner)
                    else:
                        taken.add(_hashed_uid(name, base, rng))
                for _ in range(rng):
                    if uid not in taken:
                        break
                    uid = base + ((uid - base + 1) % rng)
                else:
                    logger.warning(
                        "sandbox: uid range exhausted (%d jails in a "
                        "range of %d) — jail %s SHARES uid %d with a "
                        "live sibling; raise RAFIKI_SANDBOX_UID_RANGE",
                        len(taken), rng, jail, uid)
                os.chown(jail, uid, sandbox_gid())
            finally:
                lockf.close()  # releases the flock
        else:
            os.chown(jail, uid, sandbox_gid())
        # a pre-existing jail may hold files owned under an earlier
        # uid scheme (r4's shared 65534, or a base/range edit): rechown
        # them or the resumed child can't read its own checkpoint
        for root, dirs, files in os.walk(jail):
            for name in dirs + files:
                p = os.path.join(root, name)
                try:
                    if os.lstat(p).st_uid != uid:
                        os.lchown(p, uid, sandbox_gid())
                except OSError:
                    pass
    os.chmod(jail, 0o700)
    _ensure_traversal(os.path.dirname(jail))
    return jail


def _base_setup(jail_dir: str) -> Dict[str, Any]:
    """Isolation policy shared by trial and serve children — ONE place to
    add a new rlimit or env knob."""
    return {
        "jail_dir": jail_dir,
        "drop_uid": uid_for_jail(jail_dir),
        "drop_gid": sandbox_gid(),
        "netns": os.environ.get("RAFIKI_SANDBOX_NETNS") == "1",
        "nofile": int(os.environ.get("RAFIKI_SANDBOX_NOFILE", "1024")),
        "mem_mb": int(os.environ.get("RAFIKI_SANDBOX_MEM_MB", "0")),
    }


def _spawn_child(jail_dir: str, extra_pythonpath: Optional[str]):
    """Launch a sandbox child with the shared env policy and a bounded
    concurrent stderr drain (an undrained pipe deadlocks a chatty child;
    the tail is the only diagnostic when a child dies frameless).
    Returns (proc, stderr_chunks, drain_thread)."""
    env = _child_env(jail_dir)
    if extra_pythonpath:
        # per-model dependency prefix (sdk/deps.py) — pins shadow base
        env["PYTHONPATH"] = (
            extra_pythonpath + os.pathsep + env["PYTHONPATH"])
        _ensure_traversal(extra_pythonpath, read=True)
    # the dropped uid must still import this package — grant traversal
    # along the repo path (e.g. /root is 0700 by default) and listing on
    # the package root itself (import's FileFinder lists it)
    _ensure_traversal(_REPO_ROOT, read=True)
    # start_new_session: the child leads its OWN process group, so a
    # kill (stall/timeout watchdog, teardown) reaches every process the
    # template forked — a daemonized grandchild must not outlive its
    # trial holding a chip grant. The cost is that a SIGKILLed worker no
    # longer takes the child down via shared process group; the
    # explicit teardown paths (finally blocks here, placement destroy)
    # and the jail's resource limits bound that window.
    proc = subprocess.Popen(
        [sys.executable, "-m", "rafiki_tpu.sdk.sandbox_child"],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True,
        env=env, cwd=jail_dir, start_new_session=True,
    )
    stderr_chunks: list = []

    def _drain_stderr() -> None:
        try:
            for line in proc.stderr:
                stderr_chunks.append(line)
                if len(stderr_chunks) > 500:
                    del stderr_chunks[:250]
        except (OSError, ValueError):
            pass

    drain = threading.Thread(target=_drain_stderr, daemon=True)
    drain.start()
    return proc, stderr_chunks, drain


def _signal_group(proc, sig: int) -> None:
    """Deliver ``sig`` to the child's whole process group (it leads its
    own session — see _spawn_child), falling back to the process itself
    when the group is already gone or unsignalable."""
    try:
        os.killpg(proc.pid, sig)
        return
    except (ProcessLookupError, PermissionError, OSError):
        pass
    try:
        proc.send_signal(sig)
    except (ProcessLookupError, OSError):
        pass


def _reap_child_group(proc, grace_s: float = 10.0) -> None:
    """Teardown contract: TERM the group, wait, KILL the group, and
    sweep the group once more after the direct child is reaped so a
    forked grandchild can't outlive the trial."""
    if proc.poll() is None:
        _signal_group(proc, signal.SIGTERM)
        try:
            proc.wait(timeout=grace_s)
        except subprocess.TimeoutExpired:
            _signal_group(proc, signal.SIGKILL)
            try:
                proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                pass
    # final sweep: the group may still hold the template's forked
    # grandchildren even though the leader is reaped
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError, OSError):
        pass


def run_trial_sandboxed(
    model_bytes: bytes,
    model_class: str,
    knobs: Dict[str, Any],
    train_uri: str,
    test_uri: str,
    jail_dir: str,
    on_log_line: Callable[[str], None],
    stop_check: Optional[Callable[[Dict[str, float]], bool]] = None,
    timeout_s: Optional[float] = None,
    extra_pythonpath: Optional[str] = None,
) -> Tuple[float, bytes]:
    """Run one trial's untrusted slice in the sandbox child.

    Forwards every child log line to ``on_log_line`` (the worker's
    trial-log sink); runs ``stop_check`` on each METRICS record and sends
    the STOP verdict down the pipe when it fires — the child's logger
    then raises StopTrialEarly at its next log call, the same contract
    as the in-process wiring. Returns (score, params_bytes)."""
    setup = {
        **_base_setup(jail_dir),
        "model_b64": base64.b64encode(model_bytes).decode(),
        "model_class": model_class,
        "knobs": knobs,
        "train_uri": train_uri,
        "test_uri": test_uri,
    }
    for uri in (train_uri, test_uri):
        grant_dataset_access(uri)
    proc, stderr_chunks, stderr_thread = _spawn_child(
        jail_dir, extra_pythonpath)
    stop_sent = threading.Event()

    def send_stop() -> None:
        if stop_sent.is_set():
            return
        stop_sent.set()
        try:
            proc.stdin.write("STOP\n")
            proc.stdin.flush()
        except (BrokenPipeError, OSError, ValueError):
            pass

    result: Dict[str, Any] = {}
    rc: Optional[int] = None
    first_frame = threading.Event()
    stalled = threading.Event()
    timed_out = threading.Event()
    # Runaway guard the in-process path can't have: a template that never
    # logs cannot be stopped at a METRICS decision point, so past the
    # trial deadline the child gets a STOP (in case it logs soon), then a
    # grace period, then SIGTERM to its whole group — and, one more
    # grace period later, SIGKILL: an untrusted template may install
    # SIG_IGN for SIGTERM, and without the hard escalation the parent
    # would block on child frames forever, the exact hang class the
    # watchdogs exist to eliminate. The frame loop below unblocks on
    # EOF and the exit is classified TIMEOUT.
    watchdogs = []

    def _timeout_kill(sig: int) -> None:
        timed_out.set()
        _signal_group(proc, sig)

    if timeout_s:
        watchdogs = [
            threading.Timer(timeout_s, send_stop),
            threading.Timer(timeout_s + 60.0, _timeout_kill,
                            args=(signal.SIGTERM,)),
            threading.Timer(timeout_s + 120.0, _timeout_kill,
                            args=(signal.SIGKILL,)),
        ]
        for w in watchdogs:
            w.daemon = True
            w.start()

    # Stall watchdog (RAFIKI_TRIAL_STALL_S): without it the parent
    # blocks on child frames INDEFINITELY when the child goes mute
    # before its first line — a wedged import or a backend that never
    # came up held the executor forever. Armed only until the first frame arrives;
    # a template that has spoken is governed by TRIAL_TIMEOUT_S.
    stall_s = stall_deadline_s()

    def _stall_monitor() -> None:
        if first_frame.wait(timeout=stall_s):
            return
        if proc.poll() is None and not result:
            stalled.set()
            logger.warning(
                "sandbox child produced no frame within %.0fs "
                "(RAFIKI_TRIAL_STALL_S); killing its process group",
                stall_s)
            _signal_group(proc, signal.SIGKILL)

    if stall_s > 0:
        threading.Thread(target=_stall_monitor, daemon=True).start()
    try:
        try:
            proc.stdin.write(json.dumps(setup) + "\n")
            proc.stdin.flush()
        except (BrokenPipeError, OSError) as e:
            # spawn/interpreter-init failure: the child died before it
            # could read its setup line — the platform's fault
            raise SandboxInfraError(
                f"sandbox child died before setup ({e!r})")
        for raw in proc.stdout:
            first_frame.set()
            try:
                frame = json.loads(raw)
            except json.JSONDecodeError:
                frame = None
            if (not isinstance(frame, dict)
                    or frame.get("t") not in ("log", "done", "err")):
                # stray output that slipped past the child's stdout
                # redirection (defense in depth — including valid-JSON
                # prints and unknown-t dicts): surface it as a log line
                on_log_line(json.dumps({
                    "type": "MESSAGE", "message": raw.rstrip("\n"),
                    "time": __import__("time").time()}))
                continue
            t = frame.get("t")
            if t == "log":
                line = frame.get("line", "")
                on_log_line(line)
                if stop_check is not None and not stop_sent.is_set():
                    try:
                        rec = json.loads(line)
                    except json.JSONDecodeError:
                        rec = {}
                    if rec.get("type") == "METRICS" and stop_check(
                            rec.get("metrics") or {}):
                        send_stop()
            elif t in ("done", "err"):
                result = frame
                break
        try:
            rc = proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            # a model thread the template didn't join can keep the child
            # interpreter alive past the done frame — with the result in
            # hand that is the CHILD's problem, not the trial's (the
            # finally kills it); without a result it stays a failure
            rc = None
    finally:
        for w in watchdogs:
            w.cancel()
        first_frame.set()  # disarm the stall monitor on every exit path
        # the untrusted child is NOT abandoned on teardown (unlike
        # backend-probe children, it can hold a chip grant) — and its
        # whole process group goes with it, so forked grandchildren
        # can't outlive the trial
        _reap_child_group(proc)
        for s in (proc.stdin, proc.stdout, proc.stderr):
            try:
                s.close()
            except OSError:
                pass
        stderr_thread.join(timeout=5)
    if result.get("t") == "done":
        return float(result["score"]), base64.b64decode(result["params_b64"])
    if result.get("t") == "err":
        detail = (f"{result.get('error')}\n--- child traceback ---\n"
                  f"{result.get('traceback', '')}")
        # the child says WHO failed: model code (where=model, default
        # for old children) vs the harness itself (e.g. lockdown)
        if result.get("where", "model") != "model":
            raise SandboxInfraError(detail)
        if result.get("error_type") == "MemoryError":
            # RLIMIT_AS enforcement surfaces as MemoryError inside the
            # template — the memory envelope, not the template's logic
            raise SandboxMemError(detail)
        raise SandboxUserError(detail)
    # frameless death: classify HOW the child died (exit code vs
    # signal, which watchdog fired) instead of a generic string
    stderr_tail = "".join(stderr_chunks)[-2000:]
    if stalled.is_set():
        raise SandboxStallError(
            f"sandbox child produced no frame within "
            f"{stall_s:.0f}s (RAFIKI_TRIAL_STALL_S) and was killed; "
            f"stderr tail:\n{stderr_tail}")
    if timed_out.is_set():
        raise SandboxTimeoutError(
            f"trial exceeded its {timeout_s:.0f}s budget "
            f"(TRIAL_TIMEOUT_S) and ignored the STOP verdict; child "
            f"killed; stderr tail:\n{stderr_tail}")
    if rc is not None and rc < 0:
        try:
            signame = signal.Signals(-rc).name
        except ValueError:
            signame = f"signal {-rc}"
        if -rc == signal.SIGKILL and int(setup.get("mem_mb") or 0) > 0:
            # SIGKILL under an active memory cap is the kernel/OOM
            # enforcement path (rss breach that never surfaced as a
            # python MemoryError)
            raise SandboxMemError(
                f"sandbox child SIGKILLed with RAFIKI_SANDBOX_MEM_MB="
                f"{setup['mem_mb']} active (rss breach); stderr tail:\n"
                f"{stderr_tail}")
        raise SandboxInfraError(
            f"sandbox child killed by {signame} without a result "
            f"frame; stderr tail:\n{stderr_tail}")
    raise SandboxInfraError(
        f"sandbox child exited rc={rc} without a result frame; "
        f"stderr tail:\n{stderr_tail}")


class SandboxedModelServer:
    """Serving-side sandbox: the uploaded template answers predict batches
    from a locked-down child (same isolation policy as the trial path),
    while the trusted inference worker keeps the store, the params file,
    and the data plane. One JSON frame per batch over the pipe — the same
    wire cost the shm broker already pays per batch, so the added latency
    is encode/decode, not an extra scheduling hop. Serialized per worker:
    one batch in flight, exactly like the in-process serve loop."""

    def __init__(self, model_bytes: bytes, model_class: str,
                 knobs: Dict[str, Any], params_bytes: bytes,
                 jail_dir: str, extra_pythonpath: Optional[str] = None,
                 ready_timeout_s: float = 600.0):
        from rafiki_tpu.utils.jsonutil import dumps

        self._jail_dir = jail_dir
        self._lock = threading.Lock()
        self._proc, self._stderr_chunks, self._stderr_thread = _spawn_child(
            jail_dir, extra_pythonpath)
        # frames arrive through a reader thread + queue so every wait is a
        # REAL timeout — a silently hung child can never block the worker
        # in readline() past its deadline
        import queue as _queue

        self._frames: "_queue.Queue" = _queue.Queue()

        def _read_stdout() -> None:
            try:
                for raw in self._proc.stdout:
                    try:
                        frame = json.loads(raw)
                    except json.JSONDecodeError:
                        continue  # stray print from model code
                    if (not isinstance(frame, dict)
                            or frame.get("t") not in (
                                "ready", "preds", "err", "log")):
                        # JSON-looking print (42, [..], {"step":1}, or a
                        # dict with an unknown "t"): NOT a protocol
                        # frame — enqueuing it would pair stale answers
                        # with later queries
                        continue
                    if frame["t"] != "log":
                        self._frames.put(frame)
            except (OSError, ValueError):
                pass
            finally:
                self._frames.put(None)  # EOF sentinel, on every exit path

        self._reader = threading.Thread(target=_read_stdout, daemon=True)
        self._reader.start()
        setup = {
            **_base_setup(jail_dir),
            "mode": "serve",
            "model_b64": base64.b64encode(model_bytes).decode(),
            "model_class": model_class,
            "knobs": knobs,
            "params_b64": base64.b64encode(params_bytes).decode(),
        }
        try:
            self._proc.stdin.write(dumps(setup) + "\n")
            self._proc.stdin.flush()
        except (BrokenPipeError, OSError) as e:
            # child died before reading stdin (e.g. broken deps prefix
            # crashes interpreter init): reap it, THEN read the tail —
            # close() joins the drain thread, so the diagnostic is
            # complete rather than racing the reader
            self.close()
            tail = "".join(self._stderr_chunks)[-2000:]
            raise SandboxError(
                f"sandbox serve child died before setup ({e!r}); "
                f"stderr tail:\n{tail}")
        frame = self._next_frame(timeout_s=ready_timeout_s)
        if frame.get("t") != "ready":
            err = frame.get("error", "no ready frame")
            self.close()  # joins the stderr drain: tail is complete below
            tail = "".join(self._stderr_chunks)[-2000:]
            raise SandboxError(f"sandboxed model failed to start: {err}\n"
                               f"{frame.get('traceback', '')}\n"
                               f"stderr tail:\n{tail}")

    def _next_frame(self, timeout_s: float) -> Dict[str, Any]:
        import queue as _queue

        try:
            frame = self._frames.get(timeout=timeout_s)
        except _queue.Empty:
            return {"t": "err", "timeout": True,
                    "error": f"no frame within {timeout_s:.0f}s"}
        if frame is None:
            return {"t": "err", "error": "sandbox child exited "
                    f"(rc={self._proc.poll()})"}
        return frame

    @property
    def dead(self) -> bool:
        """True once the child can no longer serve. The worker loop exits
        on this (worker/inference.py) so placement restarts the service —
        unlike a transient model error, a dead child never recovers."""
        return self._proc.poll() is not None

    def warm_up(self) -> None:
        """No-op: the child warmed up before its ready frame — this keeps
        the object duck-compatible with a model in the worker serve loop."""

    def predict(self, queries: list) -> list:
        from rafiki_tpu import config as _config
        from rafiki_tpu.utils.jsonutil import dumps

        with self._lock:
            if self.dead:
                raise SandboxError(
                    f"sandboxed model is gone (rc={self._proc.returncode})")
            try:
                self._proc.stdin.write(dumps(
                    {"op": "predict", "queries": queries}) + "\n")
                self._proc.stdin.flush()
            except (BrokenPipeError, OSError, ValueError) as e:
                raise SandboxError(f"sandboxed model pipe broken: {e}")
            frame = self._next_frame(
                timeout_s=_config.PREDICT_TIMEOUT_S + 60.0)
            if frame.get("timeout"):
                # the in-flight answer would desynchronize every later
                # batch (stale preds for fresh queries) — a timed-out
                # child is killed AND reaped here, so `dead` is already
                # True when the worker's error handler checks it
                self._proc.kill()
                try:
                    self._proc.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    pass
                raise SandboxError(
                    f"sandboxed predict timed out; child killed: "
                    f"{frame.get('error')}")
        if frame.get("t") == "preds":
            return list(frame["predictions"])
        raise SandboxError(
            f"sandboxed predict failed: {frame.get('error')}\n"
            f"{frame.get('traceback', '')}")

    def destroy(self) -> None:
        self.close()

    def close(self) -> None:
        try:
            self._proc.stdin.write(json.dumps({"op": "exit"}) + "\n")
            self._proc.stdin.flush()
        except (BrokenPipeError, OSError, ValueError):
            pass
        try:
            self._proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pass
        # group teardown (incl. the post-reap sweep): a template that
        # forked inside the serve child must not keep answering — or
        # holding chips — after its service stops
        _reap_child_group(self._proc, grace_s=5.0)
        for s in (self._proc.stdin, self._proc.stdout, self._proc.stderr):
            try:
                s.close()
            except OSError:
                pass
        self._stderr_thread.join(timeout=5)
        # serving jails hold no resumable state (unlike trial jails)
        import shutil

        shutil.rmtree(self._jail_dir, ignore_errors=True)
