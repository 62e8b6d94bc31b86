"""Chip-affine service placement.

The reference deploys every dynamic worker as a Docker Swarm service pinned to
a node with free GPUs, tracked via node labels, and passes
``CUDA_VISIBLE_DEVICES`` (reference rafiki/container/docker_swarm.py:53-70,
99-172). A TPU host can't be time-sliced that way — chips are exclusive to a
process — so the TPU-native equivalent is an in-process *executor* model:

- ``ChipAllocator`` owns the host's device inventory (indices into
  ``jax.devices()``) — the analogue of the ``available_gpus`` node label;
- services are Python entrypoints run on daemon threads with an explicit
  *chip grant*; executors build their ``Mesh`` from exactly the granted
  devices (see rafiki_tpu.parallel.mesh), so concurrent trials occupy
  disjoint sub-slices of the host's mesh;
- the restart-on-failure contract of the reference's container layer
  (reference container_manager.py:23-25) is kept: a crashing service is
  relaunched up to ``max_restarts`` times.

``PlacementManager`` is the ABC seam (reference container_manager.py:14) so a
multi-host TPU-VM manager can replace the local one without touching the
orchestration core.
"""

from __future__ import annotations

import abc
import logging
import threading
import traceback
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

logger = logging.getLogger(__name__)


class InsufficientChipsError(Exception):
    pass


class ChipAllocator:
    """Per-host chip bookkeeping (analogue of the reference's
    `available_gpus`/`num_services` node labels,
    reference docker_swarm.py:153-169)."""

    def __init__(self, device_indices: Optional[List[int]] = None):
        """``device_indices=None`` asks ``jax.devices()`` and so
        initialises the backend HERE: for in-process (thread) placement
        only. A parent of worker processes passes an explicit inventory
        (placement/process.py ``host_chip_inventory``)."""
        if device_indices is None:
            import jax

            from rafiki_tpu.parallel.mesh import visible_devices

            all_devs = jax.devices()
            device_indices = [all_devs.index(d) for d in visible_devices()]
        self._lock = threading.Lock()
        self._free: List[int] = list(device_indices)
        self._total = list(device_indices)

    @property
    def total_chips(self) -> int:
        return len(self._total)

    @property
    def free_chips(self) -> int:
        with self._lock:
            return len(self._free)

    def allocate(self, n: int) -> List[int]:
        with self._lock:
            if n > len(self._free):
                raise InsufficientChipsError(
                    f"Requested {n} chips, only {len(self._free)} free"
                )
            grant, self._free = self._free[:n], self._free[n:]
            return grant

    def release(self, chips: List[int]) -> None:
        with self._lock:
            for c in chips:
                if c in self._total and c not in self._free:
                    self._free.append(c)
            self._free.sort()

    def claim(self, chips: List[int]) -> None:
        """Mark SPECIFIC chip indices busy (control-plane recovery: an
        adopted worker already holds its grant — the fresh allocator must
        not hand those chips to anyone else). Indices not in this host's
        inventory, or already busy, are ignored."""
        with self._lock:
            self._free = [c for c in self._free if c not in set(chips)]


@dataclass
class ServiceContext:
    """Handed to a service entrypoint: identity, chip grant, stop signal."""

    service_id: str
    service_type: str
    chips: List[int]
    stop_event: threading.Event
    extra: Dict[str, Any] = field(default_factory=dict)
    on_ready: Optional[Callable[[], None]] = None

    @property
    def stopping(self) -> bool:
        return self.stop_event.is_set()

    def ready(self) -> None:
        """Services call this once initialized (model loaded, job info read)
        — only then is the service reported RUNNING, so the deploy-time wait
        and rollback actually gate on successful startup."""
        if self.on_ready:
            self.on_ready()

    def devices(self) -> List[Any]:
        """The granted jax devices (all visible devices if the grant is
        empty — the shared-devices executor, the analogue of the
        reference's no-GPU path)."""
        import jax

        from rafiki_tpu.parallel.mesh import visible_devices

        if not self.chips:
            return visible_devices()
        all_devs = jax.devices()
        return [all_devs[i] for i in self.chips]


RunFn = Callable[[ServiceContext], None]
StatusFn = Callable[[str, str], None]  # (service_id, status)


class PlacementManager(abc.ABC):
    """ABC seam for service deployment (reference container_manager.py:14-46)."""

    @abc.abstractmethod
    def create_service(
        self,
        service_id: str,
        service_type: str,
        run_fn: RunFn,
        n_chips: int = 0,
        extra: Optional[Dict[str, Any]] = None,
    ) -> ServiceContext:
        ...

    @abc.abstractmethod
    def destroy_service(self, service_id: str, wait: bool = True) -> None:
        ...


class _ServiceRunner:
    def __init__(
        self,
        ctx: ServiceContext,
        run_fn: RunFn,
        on_status: Optional[StatusFn],
        max_restarts: int,
        on_exit: Optional[Callable[[], None]] = None,
    ):
        self.ctx = ctx
        self.run_fn = run_fn
        self.on_status = on_status
        self.max_restarts = max_restarts
        self.on_exit = on_exit
        ctx.on_ready = lambda: self._status("RUNNING")
        self.thread = threading.Thread(
            target=self._run, name=f"svc-{ctx.service_id[:8]}", daemon=True
        )

    def _status(self, status: str) -> None:
        if self.on_status:
            try:
                self.on_status(self.ctx.service_id, status)
            except Exception:
                logger.exception("status callback failed")

    def _run(self) -> None:
        # RUNNING is reported by ctx.ready() from inside run_fn, after the
        # service has actually initialized — a run_fn that crashes on startup
        # lands ERRORED without ever having claimed to run
        try:
            restarts = 0
            while not self.ctx.stop_event.is_set():
                try:
                    self.run_fn(self.ctx)
                    break  # clean exit
                except Exception:
                    logger.error(
                        "service %s crashed:\n%s",
                        self.ctx.service_id,
                        traceback.format_exc(),
                    )
                    restarts += 1
                    if restarts > self.max_restarts:
                        self._status("ERRORED")
                        return
                    # restart-on-failure, like the swarm restart policy
            self._status("STOPPED")
        finally:
            # chips are released here — only once the thread has actually
            # stopped touching its granted devices, whatever the exit path
            # (clean, stopped, or errored past max_restarts)
            if self.on_exit:
                self.on_exit()


class LocalPlacementManager(PlacementManager):
    """Runs services as daemon threads on this host with chip grants."""

    def __init__(
        self,
        allocator: Optional[ChipAllocator] = None,
        on_status: Optional[StatusFn] = None,
        max_restarts: int = 3,
    ):
        self.allocator = allocator or ChipAllocator()
        self.on_status = on_status
        self.max_restarts = max_restarts
        self._lock = threading.Lock()
        self._runners: Dict[str, _ServiceRunner] = {}

    def create_service(
        self,
        service_id: str,
        service_type: str,
        run_fn: RunFn,
        n_chips: int = 0,
        extra: Optional[Dict[str, Any]] = None,
        best_effort_chips: bool = False,
    ) -> ServiceContext:
        """Deploy a service. With ``best_effort_chips``, a grant that can't be
        satisfied falls back to no exclusive grant (shared devices) instead of
        failing — used for serving executors that should prefer, but not
        require, their own chip."""
        try:
            chips = self.allocator.allocate(n_chips) if n_chips > 0 else []
        except InsufficientChipsError:
            if not best_effort_chips:
                raise
            chips = []
        ctx = ServiceContext(
            service_id=service_id,
            service_type=service_type,
            chips=chips,
            stop_event=threading.Event(),
            extra=extra or {},
        )
        runner = _ServiceRunner(
            ctx,
            run_fn,
            self.on_status,
            self.max_restarts,
            on_exit=lambda: self.allocator.release(ctx.chips),
        )
        with self._lock:
            self._runners[service_id] = runner
        runner.thread.start()
        return ctx

    def destroy_service(self, service_id: str, wait: bool = True) -> None:
        with self._lock:
            runner = self._runners.pop(service_id, None)
        if runner is None:
            return  # tolerate concurrent deletion (reference
            # services_manager.py:274-277 logged and moved on)
        runner.ctx.stop_event.set()
        if wait:
            runner.thread.join(timeout=30)
        # chip release happens in the runner's exit hook, once the thread is
        # actually off the devices

    def list_services(self) -> List[Dict[str, Any]]:
        """Enumerate this host's LIVE executors — the inventory a
        restarted control plane reconciles the store against
        (placement/agent.py GET /inventory; docs/failure-model.md
        "Control-plane faults"). Finished runners (their terminal rows
        are already in the store) are not part of the running-set."""
        with self._lock:
            runners = dict(self._runners)
        return [
            {
                "service_id": sid,
                "service_type": r.ctx.service_type,
                "status": "RUNNING",
                "chips": list(r.ctx.chips),
                # inventory schema parity with the process engine: thread
                # executors have no pid of their own
                "pid": None,
            }
            for sid, r in runners.items()
            if r.thread.is_alive()
        ]

    def stop_all(self) -> None:
        with self._lock:
            ids = list(self._runners)
        for sid in ids:
            self.destroy_service(sid)
