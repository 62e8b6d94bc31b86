"""What every cell's run shares: the look for a chip, the table of peaks, the
in-process platform (Admin + AdminServer over this process's chip, driven by a
Client over HTTP), compile metering, the device's memory peak and the profiler
window. Copied from chip_smoke.py (`_boot`, `CompileMeter`, `_peak_bytes`),
which proved these on the chip in PR 21; nothing here is imported from it.
"""

from __future__ import annotations

import functools
import importlib
import json
import os
import re
import shutil
import tempfile
import threading
import time
from dataclasses import dataclass
from typing import Any

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SEED_MOD = 2147483629  # a prime under 2**31: seed + 1 still fits an int32


@dataclass
class Context:
    """What a traffic kind is handed."""

    devices: list
    peaks: dict
    meter: Any
    seed: int
    seconds: float
    trace: bool
    out_dir: str
    t_start: float  # wall time at which the process started


class BenchmarkError(RuntimeError):
    """The run cannot measure: no result line is printed, exit code != 0."""


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts), encoding="utf-8") as f:
        return json.load(f)


def load_by_name(package: str, name: str):
    """The module `benchmark/<package>/<name>.py`: kinds, readers, families,
    references and operation counts are found by the name BENCHMARK.json, a
    configuration file or a traffic file gives, never by an edit to a file
    that is there."""
    if not re.fullmatch(r"[A-Za-z0-9_.\-]+", name):
        raise BenchmarkError(f"bad name {name!r}")
    return importlib.import_module(
        f"benchmark.{package}.{name.replace('.', '_').replace('-', '_')}")


def template_values(cfg: dict) -> dict:
    """The `# @cell` values a configuration sets in its template: its
    `template.values` maps each name to a key of the same file, dotted for
    a nested one. A kind adds what the seed and the traffic file give."""
    values = {}
    for name, key in cfg["template"]["values"].items():
        try:
            values[name] = functools.reduce(lambda d, k: d[k],
                                            key.split("."), cfg)
        except (KeyError, TypeError):
            raise BenchmarkError(f"template.values: {name} names {key!r}, "
                                 f"which the configuration does not have")
    return values


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def load_cell(workload: str) -> dict:
    """BENCHMARK.json's entry for `workload`, with its configuration and
    traffic files read in."""
    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchmarkError(f"unknown workload {workload!r}; BENCHMARK.json "
                             f"has {sorted(cells)}")
    cell = dict(cells[workload])
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, cfg_entry["file"]), encoding="utf-8") as f:
        cell["config_data"] = json.load(f)
    cell["traffic_data"] = load_json("traffic", cell["traffic"] + ".json")
    reports = lambda m: "workloads" not in m or workload in m["workloads"]
    cell["end_to_end"] = [m for m in bench["end_to_end"] if reports(m)]
    cell["per_layer"] = [m for m in bench["per_layer"] if reports(m)]
    return cell


def within_limits(checks: dict) -> bool:
    """Marks each number compared `ok` or not against its limit; whether
    all are. The one place that decides, for a run and for a control."""
    for c in checks.values():
        c["ok"] = bool(c["value"] <= c["limit"])
    return all(c["ok"] for c in checks.values())


# -- the chip ----------------------------------------------------------------

def find_chip(chips: int):
    """The devices this run measures on. No CPU branch: without a TPU, with
    fewer chips than the cell asks for, or with a device kind that
    peaks.json does not list, the run fails before it prints anything."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchmarkError(
            f"no accelerator: jax.devices()[0].platform is "
            f"{devices[0].platform!r}; the benchmark measures on a TPU only")
    if len(devices) < chips:
        raise BenchmarkError(f"the cell needs {chips} chips, this process "
                             f"has {len(devices)}")
    return devices[:chips], peaks_for(devices[0].device_kind)


def peaks_for(device_kind: str) -> dict:
    peaks = load_json("peaks.json")["device_kinds"]
    if device_kind not in peaks:
        raise BenchmarkError(f"device kind {device_kind!r} is not in "
                             f"benchmark/peaks.json ({sorted(peaks)})")
    return peaks[device_kind]


def device_record(devices) -> dict:
    import jax

    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": memory_peak_bytes(devices)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes held on the fullest chip, as the backend reports them. The
    TPU runtime counts two things apart: the arrays the program holds
    (`peak_bytes_in_use`: weights, optimizer state, the KV pool, data) and
    what XLA reserves for a compiled program while it runs
    (`peak_bytes_reserved`: activations, gradients, gathered views). The
    chip holds both at once, the second while the first is at its peak, so
    the peak is their sum; PERF.md gives both parts of every cell."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0))
                     + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks)


def memory_parts(devices) -> dict:
    stats = devices[0].memory_stats() or {}
    return {k: int(stats.get(k, 0))
            for k in ("peak_bytes_in_use", "peak_bytes_reserved")}


def bytes_in_use(devices) -> int:
    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in devices)


class CompileMeter:
    """Seconds this process spent in XLA backend compiles (cache retrievals
    included), programs compiled and persistent-cache hits, from JAX's
    monitoring events. Readers take deltas of `snapshot()`."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.programs = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1

    def _on_event(self, event: str, **kw) -> None:
        if event.endswith("/compilation_cache/cache_hits"):
            self.hits += 1

    def snapshot(self) -> dict:
        return {"seconds": self.seconds, "programs": self.programs,
                "hits": self.hits}

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_dur)
        jax.monitoring.unregister_event_listener(self._on_event)


# -- the platform, in this process --------------------------------------------

class Platform:
    """Admin + AdminServer in this process with thread placement over the
    chips it owns, a fresh work directory, and logged-in Clients over HTTP.
    Thread placement is the one arrangement in which the process that
    measures is the process that holds the chip and can trace it."""

    def __init__(self, n_chips: int, settings: dict | None = None):
        self.workdir = tempfile.mkdtemp(prefix="rafiki_bench_")
        os.environ["RAFIKI_WORKDIR"] = self.workdir
        os.environ["RAFIKI_PREDICTOR_PORTS"] = "1"
        # a cell's traffic file may list program settings that have no
        # budget key: they go into the environment before the admin boots
        for key, value in (settings or {}).get("env", {}).items():
            os.environ[key] = str(value)

        from rafiki_tpu import config
        from rafiki_tpu.admin.admin import Admin
        from rafiki_tpu.admin.http import AdminServer
        from rafiki_tpu.db.database import Database
        from rafiki_tpu.placement.manager import (ChipAllocator,
                                                  LocalPlacementManager)
        from rafiki_tpu.sdk import compile_cache

        compile_cache.enable()
        self._config = config
        self.admin = Admin(
            db=Database(":memory:"),
            placement=LocalPlacementManager(
                allocator=ChipAllocator(list(range(n_chips)))),
            params_dir=os.path.join(self.workdir, "params"))
        self.server = AdminServer(self.admin, port=0).start()
        self.port = self.server.port

    def login(self):
        from rafiki_tpu.client.client import Client

        client = Client("127.0.0.1", self.port)
        client.login(self._config.SUPERADMIN_EMAIL,
                     self._config.SUPERADMIN_PASSWORD)
        return client

    def credentials(self) -> dict:
        return {"host": "127.0.0.1", "port": self.port,
                "email": self._config.SUPERADMIN_EMAIL,
                "password": self._config.SUPERADMIN_PASSWORD}

    def close(self) -> None:
        try:
            self.admin.stop_all_jobs()
            self.server.stop()
            self.admin.shutdown()
        finally:
            shutil.rmtree(self.workdir, ignore_errors=True)


def render_template(config_name: str, values: dict, out_dir: str) -> str:
    """Write `configs/<config>_template.py` with its `# @cell` lines set to
    this cell's values, every one of them, and return the path to upload.
    The template on disk is valid Python at a tiny size."""
    with open(os.path.join(HERE, "configs", f"{config_name}_template.py"),
              encoding="utf-8") as f:
        lines = f.read().split("\n")
    at = {m.group(1): i for i, m in enumerate(
        re.match(r"^([A-Z_]+) = .*# @cell$", line) for line in lines) if m}
    if set(at) != set(values):  # a line left unset would run at its tiny size
        raise BenchmarkError(
            f"template {config_name}: no # @cell line for "
            f"{sorted(set(values) - set(at))}, no value for "
            f"{sorted(set(at) - set(values))}")
    for name, i in at.items():
        lines[i] = f"{name} = {values[name]!r}  # @cell"
    path = os.path.join(out_dir, f"{config_name}_template.py")
    with open(path, "w", encoding="utf-8") as f:
        f.write("\n".join(lines))
    return path


def wait_for(predicate, timeout_s: float, what: str, every_s: float = 0.1):
    deadline = time.monotonic() + timeout_s
    while True:
        got = predicate()
        if got:
            return got
        if time.monotonic() > deadline:
            raise BenchmarkError(f"timed out after {timeout_s:.0f}s waiting "
                                 f"for {what}")
        time.sleep(every_s)


# -- the profiler window -------------------------------------------------------

class TraceWindow:
    """`jax.profiler` around a few seconds of the measured window, started and
    stopped by a timer thread so that the window's own driver is not held up.
    Only a `--trace 1` run makes one."""

    def __init__(self, out_dir: str, start_at: float, seconds: float):
        self.dir = os.path.join(out_dir, "trace")
        shutil.rmtree(self.dir, ignore_errors=True)
        self.start_at = start_at
        self.seconds = seconds
        self.t0 = self.t1 = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-trace")
        self._thread.start()

    def _run(self) -> None:
        import jax

        time.sleep(max(self.start_at - time.time(), 0.0))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        self.t0 = time.time()
        jax.profiler.start_trace(self.dir, profiler_options=options)
        try:
            time.sleep(self.seconds)
        finally:
            t1 = time.time()  # writing the trace out takes many seconds
            jax.profiler.stop_trace()
            self.t1 = t1

    def finish(self) -> str | None:
        """Wait for the trace to be written; the path of its .xplane.pb."""
        self._thread.join(timeout=240)
        if self._thread.is_alive() or self.t1 is None:
            raise BenchmarkError("the profiler did not stop")
        for base, _, files in os.walk(self.dir):
            for name in files:
                if name.endswith(".xplane.pb"):
                    return os.path.join(base, name)
        return None
