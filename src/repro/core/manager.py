"""Device discovery and kernel-actor spawning (paper Fig. 2: manager /
platform / device).

* ``Platform`` wraps a JAX backend (the analogue of an OpenCL platform —
  an entry point provided by a driver).
* ``Device`` wraps a ``jax.Device`` and tracks an outstanding-dispatch
  counter, the analogue of the per-device command queue.
* ``DeviceManager`` is the ``actor_system`` module that "performs platform
  discovery lazily on first access and offers an interface to spawn OpenCL
  actors" (paper §3.2).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import jax

from ..analysis.runtime import make_lock
from .signature import NDRange

__all__ = ["Platform", "Device", "DeviceManager"]


class Device:
    """An accelerator device with a dispatch (command-queue) counter and
    live-memory watermarks (fed by the DeviceRef registry)."""

    def __init__(self, jax_device: jax.Device, platform: "Platform"):
        self.jax_device = jax_device
        self.platform = platform
        self._inflight = 0
        self._lock = make_lock("Device")

    @property
    def name(self) -> str:
        return f"{self.jax_device.platform}:{self.jax_device.id}"

    @property
    def device_kind(self) -> str:
        return self.jax_device.device_kind

    def queue_depth(self) -> int:
        return self._inflight

    # -- memory watermarks (DeviceRef registry) -------------------------------
    def live_bytes(self) -> int:
        """Bytes currently held by live DeviceRefs on this device."""
        from .memref import registry
        return registry.live_bytes(self.jax_device)

    def peak_bytes(self) -> int:
        """High watermark of DeviceRef bytes ever resident on this device."""
        from .memref import registry
        return registry.peak_bytes(self.jax_device)

    def page_stats(self) -> dict:
        """KV page-pool pressure on this device (aggregated over every
        :class:`repro.serve.kvpool.PagePool` allocated here): capacity,
        live/free/shared pages, and the fragmentation ratio."""
        from .memref import registry
        return registry.page_stats(self.jax_device)

    def _dispatch_started(self):
        with self._lock:
            self._inflight += 1

    def _dispatch_finished(self):
        with self._lock:
            self._inflight -= 1

    def __repr__(self):
        return (f"Device({self.name}, inflight={self._inflight}, "
                f"live_bytes={self.live_bytes()})")


class Platform:
    def __init__(self, backend: str, devices: Sequence[jax.Device]):
        self.name = backend
        self.devices = [Device(d, self) for d in devices]

    def __repr__(self):
        return f"Platform({self.name}, {len(self.devices)} devices)"


class DeviceManager:
    """Lazily discovers platforms and spawns kernel actors (paper §3.2)."""

    def __init__(self, system):
        self.system = system
        self._platforms: Optional[list[Platform]] = None
        self._lock = make_lock("DeviceManager")

    # -- discovery ------------------------------------------------------
    @property
    def platforms(self) -> list[Platform]:
        with self._lock:
            if self._platforms is None:
                self._platforms = self._discover()
            return self._platforms

    def _discover(self) -> list[Platform]:
        by_backend: Dict[str, list] = {}
        for d in jax.devices():
            by_backend.setdefault(d.platform, []).append(d)
        return [Platform(k, v) for k, v in sorted(by_backend.items())]

    def devices(self) -> list[Device]:
        return [d for p in self.platforms for d in p.devices]

    def find_device(self, *, platform: Optional[str] = None, index: int = 0) -> Device:
        """Default binding is the first discovered device (paper §3.6)."""
        devs = self.devices()
        if platform is not None:
            devs = [d for d in devs if d.jax_device.platform == platform]
        if not devs:
            raise LookupError(f"no device for platform={platform!r}")
        return devs[index]

    def memory_stats(self) -> Dict[str, Dict[str, Any]]:
        """Per-device memory watermarks: live DeviceRef bytes, the peak
        (high watermark), current dispatch queue depth — the signals the
        pool's least-loaded policy ranks by — plus KV page-pool pressure
        (``pages_total``/``pages_free``/``pages_shared`` and the
        fragmentation ratio) wherever a ``repro.serve.kvpool.PagePool``
        lives on the device."""
        out: Dict[str, Dict[str, Any]] = {}
        for d in self.devices():
            ps = d.page_stats()
            out[d.name] = {"live_bytes": d.live_bytes(),
                           "peak_bytes": d.peak_bytes(),
                           "queue_depth": d.queue_depth(),
                           "pages_total": ps["pages_total"],
                           "pages_free": ps["pages_free"],
                           "pages_shared": ps["pages_shared"],
                           "fragmentation": ps["fragmentation"]}
        return out

    def pick_device(self, *, context: str = "manager") -> Device:
        """Cost-ranked device choice through the process-wide
        :class:`~repro.core.placement.PlacementService` (least live
        DeviceRef bytes, then queue depth, deterministic name tie-break)
        — the load-aware counterpart of :meth:`find_device`'s static
        first-discovered binding. The decision lands in the service's
        audit ring like every other placement."""
        from .placement import service as placement_service
        return placement_service().pick_device(self.devices(),
                                               context=context).chosen

    # -- actor creation -------------------------------------------------
    def spawn(self, source, name: Optional[str] = None,
              nd_range: Optional[NDRange] = None, *specs, **kwargs):
        """Spawn an OpenCL actor (paper Listing 2/3/5).

        ``source`` is a :func:`repro.core.kernel`-decorated callable (a
        :class:`~repro.core.api.KernelDecl`) that already carries its
        signature and ND-range; ``name``/``nd_range``/``*specs`` and the
        ``preprocess``/``postprocess``/``donate`` keywords act as
        per-spawn overrides of the declaration, ``device=`` places the
        actor, and ``emit``/``lazy_init`` are passed to the actor.
        """
        from .api import KernelDecl     # local import: avoid cycle
        from .facade import KernelActor
        if not isinstance(source, KernelDecl):
            raise TypeError(
                f"cannot spawn {source!r} as a kernel actor: declare it "
                "with @repro.core.kernel(In(...), Out(...), nd_range=...)")
        overrides = {}
        if name is not None:
            overrides["name"] = name
        if nd_range is not None:
            overrides["nd_range"] = nd_range
        if specs:
            overrides["specs"] = specs
        for opt in ("preprocess", "postprocess", "donate"):
            if opt in kwargs:
                overrides[opt] = kwargs.pop(opt)
        decl = source.with_options(**overrides) if overrides else source
        device = kwargs.pop("device", None) or self.find_device()
        lazy_init = kwargs.pop("lazy_init", True)
        emit = kwargs.pop("emit", "declared")
        if kwargs:
            raise TypeError(f"unknown spawn options: {sorted(kwargs)}")
        actor = KernelActor(fn=decl.fn, name=decl.name,
                            nd_range=decl.nd_range, specs=decl.specs,
                            device=device, preprocess=decl.preprocess,
                            postprocess=decl.postprocess,
                            donate=decl.donate, emit=emit)
        return self.system.spawn(actor, lazy_init=lazy_init)

    def spawn_pool(self, source, n: int, *, policy: str = "round_robin",
                   devices: Optional[Sequence[Device]] = None,
                   default_timeout: Optional[float] = 120.0, **kwargs):
        """Spawn ``n`` replicas of a kernel behind one pool ref.

        Replicas are placed round-robin over ``devices`` (default: every
        discovered device); the returned :class:`~repro.core.api.ActorPool`
        routes per ``policy`` ("round_robin" | "least_loaded", the latter
        keyed on outstanding requests then ``Device.queue_depth()``) and
        plugs into :class:`~repro.core.scheduler.ChunkScheduler`.
        ``default_timeout`` becomes the pool's ``ask`` timeout (None =
        wait forever).
        """
        from .api import ActorPool
        if n < 1:
            raise ValueError("pool size must be >= 1")
        devs = list(devices) if devices else self.devices()
        refs, placed = [], []
        for i in range(n):
            dev = devs[i % len(devs)]
            refs.append(self.spawn(source, device=dev, **kwargs))
            placed.append(dev)
        return ActorPool(self.system, refs, policy=policy, devices=placed,
                         default_timeout=default_timeout)
