"""The load generator of the generate cells: a child process that holds no
chip (it is started with JAX_PLATFORMS=cpu and never runs a JAX operation),
drives `Client.generate` over HTTP and records, for every request, when it
was sent and when each delta arrived.

    python -m benchmark.loadgen <spec.json>

`spec.json`: host, port, email, password, app, callers, seconds, requests
(each: prompt_ids, max_tokens), out. The child logs
in, resolves the per-job door once (a route re-resolved inside a timed call
corrupts the tail), sends one warm request, prints READY, and reads the wall
time at which the window opens from its standard input. After the window it
lets requests in flight finish (a minute at the most), writes `out` and ends.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

GRACE_S = 60.0


def _client(spec: dict):
    from rafiki_tpu.client.client import Client

    client = Client(spec["host"], spec["port"])
    client.login(spec["email"], spec["password"])
    return client


def _send(client, spec: dict, request: dict, record: dict) -> None:
    """One stream, every delta stamped as it arrives."""
    record["sent"] = time.time()
    try:
        for delta in client.generate(spec["app"], request["prompt_ids"],
                                     max_tokens=request["max_tokens"],
                                     timeout_s=120.0):
            now = time.time()
            tokens = delta.get("tokens") or []
            if tokens:
                record["deltas"].append([now, len(tokens)])
                record["tokens"].extend(int(t) for t in tokens)
            if delta.get("finished"):
                record["reason"] = delta.get("reason")
    except Exception as e:  # recorded as a failed stream, never swallowed
        record["error"] = f"{type(e).__name__}: {e}"
    record["done"] = time.time()


def _record(i: int, request: dict) -> dict:
    return {"i": i, "sent": None, "done": None, "deltas": [],
            "tokens": [], "reason": None, "error": None,
            "prompt_tokens": len(request["prompt_ids"]),
            "max_tokens": request["max_tokens"]}


def run_closed(spec: dict, t0: float, records: list, clients: list) -> None:
    """`callers` callers, each sending its next request as the last ends."""
    t1 = t0 + spec["seconds"]
    lock = threading.Lock()
    cursor = [0]

    def caller(client) -> None:
        while time.time() < t0:
            time.sleep(min(max(t0 - time.time(), 0.0), 0.01))
        while True:
            with lock:
                i = cursor[0]
                cursor[0] += 1
            if i >= len(spec["requests"]) or time.time() >= t1:
                return
            record = _record(i, spec["requests"][i])
            with lock:
                records.append(record)
            _send(client, spec, spec["requests"][i], record)

    threads = [threading.Thread(target=caller, args=(c,), daemon=True)
               for c in clients]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=max(t1 + GRACE_S - time.time(), 0.1))


def main(argv: list) -> int:
    with open(argv[0], encoding="utf-8") as f:
        spec = json.load(f)
    # the route's TTL is the client's own setting; keep it past the window
    os.environ.setdefault("PREDICT_ROUTE_TTL_S", "3600")
    clients = [_client(spec) for _ in range(spec["callers"])]
    for client in clients:  # the per-job door, resolved once
        client._dedicated_door(spec["app"], -1)
    warm = _record(-1, spec["warm"])
    _send(clients[0], spec, spec["warm"], warm)
    if warm["error"]:
        print(f"loadgen: warm request failed: {warm['error']}",
              file=sys.stderr)
        return 1
    print("READY", flush=True)
    t0 = float(sys.stdin.readline())
    records: list = []
    run_closed(spec, t0, records, clients)
    with open(spec["out"], "w", encoding="utf-8") as f:
        json.dump({"t0": t0, "records": records}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
