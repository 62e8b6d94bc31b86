"""Sandbox child: the untrusted half of a trial (see sdk/sandbox.py).

Reads one setup JSON line on stdin, locks itself down (rlimits, cwd
jail, uid drop when launched by a root worker), then runs the model
template's train -> evaluate -> dump_parameters cycle, streaming logger
lines as frames on stdout and finishing with a done/err frame. A
``STOP`` line on stdin (the worker's mid-trial verdict) flips a flag the
logger's stop-check reads — the next ``log()`` raises StopTrialEarly,
identical to the in-process wiring (worker/train.py _install_stop_check).

Isolation happens HERE, in the child, before any untrusted byte is
imported; the parent only chooses the policy. Frames are written before
the uid drop could matter: stdout/stderr are inherited pipes, writable
regardless of uid.
"""

from __future__ import annotations

import base64
import json
import os
import resource
import sys
import threading
import traceback


# The protocol channel is a PRIVATE dup of the original stdout fd,
# claimed before any untrusted code runs (_claim_protocol_channel): model
# prints — Python-level or C-level fd-1 writes — can then never be read
# as protocol frames (the desync class the parent-side filters only
# mitigate). Until claimed, frames go to plain stdout (e.g. lockdown
# errors).
_PROTO = sys.stdout


def _emit(frame: dict) -> None:
    # shared wire convention: numpy converts at any depth (a model's
    # predictions may nest arrays/scalars inside dicts/lists)
    from rafiki_tpu.utils.jsonutil import dumps

    _PROTO.write(dumps(frame) + "\n")
    _PROTO.flush()


class _PrintsToLogFrames:
    """sys.stdout replacement: model print() output becomes MESSAGE log
    frames on the protocol channel, line-buffered."""

    def __init__(self) -> None:
        self._buf = ""

    def write(self, text: str) -> int:
        import time

        self._buf += text
        while "\n" in self._buf:
            line, self._buf = self._buf.split("\n", 1)
            if line:
                _emit({"t": "log", "line": json.dumps({
                    "type": "MESSAGE", "message": line,
                    "time": time.time()})})
        return len(text)

    def flush(self) -> None:
        pass

    def isatty(self) -> bool:
        return False


def _claim_protocol_channel() -> None:
    """Make fd 1 unusable for protocol corruption: the harness keeps a
    private dup for frames, raw fd-1 writes land in stderr (drained by
    the parent), and Python-level prints become log frames."""
    global _PROTO

    _PROTO = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    sys.stdout = _PrintsToLogFrames()


def _unshare_netns() -> None:
    """Detach from the host network namespace (opt-in,
    RAFIKI_SANDBOX_NETNS=1): the child keeps only a down loopback, so it
    cannot reach the admin/agent control plane or dial out at all. Must
    run before the uid drop (needs CAP_SYS_ADMIN); incompatible with
    trials that need sockets (e.g. a dataset fetched over HTTP)."""
    import ctypes

    CLONE_NEWNET = 0x40000000
    libc = ctypes.CDLL(None, use_errno=True)
    if libc.unshare(CLONE_NEWNET) != 0:
        err = ctypes.get_errno()
        raise OSError(err, "unshare(CLONE_NEWNET): " + os.strerror(err))


def _no_new_privs() -> None:
    """prctl(PR_SET_NO_NEW_PRIVS): execve of setuid/setcap binaries can
    never re-escalate this process tree. Best-effort (old kernels)."""
    import ctypes

    try:
        ctypes.CDLL(None, use_errno=True).prctl(38, 1, 0, 0, 0)
    # lint: absorb(prctl hardening is best-effort on old kernels)
    except Exception:
        pass


def _lockdown(setup: dict) -> None:
    resource.setrlimit(resource.RLIMIT_CORE, (0, 0))
    nofile = int(setup.get("nofile") or 0)
    if nofile:
        resource.setrlimit(resource.RLIMIT_NOFILE, (nofile, nofile))
    mem_mb = int(setup.get("mem_mb") or 0)
    if mem_mb:
        cap = mem_mb << 20
        resource.setrlimit(resource.RLIMIT_AS, (cap, cap))
    os.chdir(setup["jail_dir"])
    drop_uid = setup.get("drop_uid")
    if setup.get("netns") and os.geteuid() != 0:
        # fail LOUDLY: silently skipping would leave the operator
        # believing loopback is unreachable when it isn't
        raise PermissionError(
            "RAFIKI_SANDBOX_NETNS=1 requires a root worker "
            "(unshare(CLONE_NEWNET) needs CAP_SYS_ADMIN)")
    if os.geteuid() == 0:
        if setup.get("netns"):
            _unshare_netns()
        if drop_uid:
            # FULL credential drop: supplementary groups cleared, gid
            # dropped to the sandbox gid (65534 by default — gid 0 is
            # retained only when the operator sets
            # RAFIKI_SANDBOX_KEEP_GID0=1 for deployments whose TPU
            # device nodes are group-0 gated), then the per-trial uid.
            # Group-root files (0640 root:root) and sibling trials'
            # 0700 jails are unreachable; world-readable code (repo,
            # venv, stdlib) stays importable — the protection boundary
            # of the threat model in sdk/sandbox.py.
            os.setgroups([])
            os.setgid(int(setup.get("drop_gid", 65534)))
            os.setuid(int(drop_uid))
    _no_new_privs()


def main() -> int:
    setup = json.loads(sys.stdin.readline())
    try:
        _lockdown(setup)
    # lint: absorb(the err frame carries the failure to the parent as INFRA)
    except Exception:
        # where=lockdown: the HARNESS failed, not the template — the
        # parent classifies this INFRA (retryable), never USER
        _emit({"t": "err", "error": "sandbox lockdown failed",
               "where": "lockdown",
               "traceback": traceback.format_exc()})
        return 3

    _claim_protocol_channel()

    if setup.get("mode") == "serve":
        return _serve(setup)

    stop_flag = threading.Event()

    def stdin_watcher() -> None:
        for line in sys.stdin:
            if line.strip() == "STOP":
                stop_flag.set()

    threading.Thread(target=stdin_watcher, daemon=True).start()

    try:
        from rafiki_tpu.sdk.log import ModelLogger, StopTrialEarly
        from rafiki_tpu.sdk.model import load_model_class
        from rafiki_tpu.sdk.params import dump_params

        clazz = load_model_class(
            base64.b64decode(setup["model_b64"]), setup["model_class"])
        model = clazz(**setup["knobs"])
        model_logger = ModelLogger()
        model_logger.set_sink(lambda line: _emit({"t": "log", "line": line}))
        model_logger.set_stop_check(lambda metrics: stop_flag.is_set())
        model.logger = model_logger
        model.checkpoint_path = os.path.join(
            setup["jail_dir"], "trial.ckpt")
        try:
            try:
                model.train(setup["train_uri"])
            except StopTrialEarly:
                model_logger.log("trial stopped early by scheduler")
            model_logger.set_stop_check(None)
            score = float(model.evaluate(setup["test_uri"]))
            params_b64 = base64.b64encode(
                dump_params(model.dump_parameters())).decode()
        finally:
            model.destroy()
        _emit({"t": "done", "score": score, "params_b64": params_b64})
        return 0
    # lint: absorb(the err frame carries the failure to the parent for fault classification)
    except Exception as e:
        # error_type lets the parent map the failure into the fault
        # classification (MemoryError -> MEM, everything else -> USER)
        # without parsing the message
        _emit({"t": "err", "error": f"{type(e).__name__}: {e}",
               "where": "model", "error_type": type(e).__name__,
               "traceback": traceback.format_exc()[-4000:]})
        return 1


def _serve(setup: dict) -> int:
    """Serving mode: load the template + TRUSTED-side-supplied params,
    warm up, then answer predict frames until stdin closes. One frame in
    ({"op":"predict","queries":[...]}), one frame out ({"t":"preds"} or
    {"t":"err"}) — a per-query error fails only that batch, never the
    loop (parity with worker/inference.py's in-process error handling)."""
    try:
        from rafiki_tpu.sdk.model import load_model_class
        from rafiki_tpu.sdk.params import load_params

        clazz = load_model_class(
            base64.b64decode(setup["model_b64"]), setup["model_class"])
        model = clazz(**setup["knobs"])
        model.load_parameters(
            load_params(base64.b64decode(setup["params_b64"])))
        try:
            model.warm_up()
        # lint: absorb(warm_up is optional; the failure is logged to the trial log frame)
        except Exception:
            _emit({"t": "log", "line": json.dumps({
                "type": "MESSAGE",
                "message": "warm_up failed in sandbox (serving anyway)",
                "time": 0})})
        _emit({"t": "ready"})
    # lint: absorb(warm_up is optional; the failure is logged to the trial log frame)
    except Exception as e:
        _emit({"t": "err", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]})
        return 1
    try:
        for line in sys.stdin:
            try:
                frame = json.loads(line)
            except json.JSONDecodeError:
                continue
            if frame.get("op") == "exit":
                break
            if frame.get("op") != "predict":
                continue
            try:
                preds = model.predict(frame["queries"])
                _emit({"t": "preds", "predictions": list(preds)})
            # lint: absorb(per-request err frame; the serving loop must survive template bugs)
            except Exception as e:
                _emit({"t": "err", "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]})
        return 0
    finally:
        model.destroy()


if __name__ == "__main__":
    sys.exit(main())
