"""Memory-access modes (paper Fig. 1): DM / DC / DevMem, adapted to the
TPU host-offload setting.

  DM     — weights live in HOST memory; every use streams them to the
           device, no reuse cache (paper: DMA straight to DRAM, arrows
           3,5 — bypasses the LLC).
  DC     — like DM plus a device-side LRU page cache (the "LLC",
           arrows 2,4,5): hot tiles are served at device speed.
  DevMem — weights resident in device memory (arrow 6): no host traffic
           during compute, but host-side stages pay the crossing.

On real hardware the placement uses ``memory_kind="pinned_host"`` vs
``"device"``; on the CPU backend (no distinct host space) the semantics
are preserved and all traffic is metered, which is what the benchmarks
and the accesys simulator consume.
"""
from __future__ import annotations

import collections
import dataclasses
import enum
from typing import Optional

import jax
import jax.numpy as jnp


class MemoryMode(enum.Enum):
    DM = "DM"
    DC = "DC"
    DEVMEM = "DevMem"


def host_placement(x):
    """Place an array in host memory.

    We keep host-resident data as NUMPY arrays: genuinely host RAM on
    every backend, and it sidesteps jax's sticky <host> memory-space
    avals on sliced pinned_host buffers (device_put of a numpy array is
    the portable H2D DMA).
    """
    import numpy as np
    return np.asarray(jax.device_get(x))


def device_placement(x):
    return jax.device_put(x, jax.devices()[0])


@dataclasses.dataclass
class TrafficStats:
    host_to_device_bytes: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    lookups: int = 0

    @property
    def hit_rate(self) -> float:
        return self.cache_hits / max(self.lookups, 1)


class PageStore:
    """Mode-aware page provider: the software half of the co-design.

    ``get(page_id)`` returns the page on-device, metering the traffic the
    chosen mode implies. DevMem: everything resident. DM: every access
    streams host→device. DC: LRU cache of ``cache_pages`` (the LLC).
    """

    def __init__(self, pages: dict, mode: MemoryMode,
                 cache_pages: int = 512):
        self.mode = mode
        self.stats = TrafficStats()
        self._page_bytes: dict = {}
        self._resident: dict = {} if mode is MemoryMode.DEVMEM else None
        self._host: dict = None if mode is MemoryMode.DEVMEM else {}
        self._cache: "collections.OrderedDict" = collections.OrderedDict()
        self._cache_pages = cache_pages
        self.add_pages(pages)

    def add_pages(self, pages: dict) -> None:
        """Register pages after construction — intermediates produced
        mid-plan (an upstream op's DMA-out becomes a downstream operand)
        land host-side in DM/DC and resident in DevMem."""
        self._page_bytes.update({k: int(v.size * v.dtype.itemsize)
                                 for k, v in pages.items()})
        if self.mode is MemoryMode.DEVMEM:
            self._resident.update({k: device_placement(v)
                                   for k, v in pages.items()})
        else:
            self._host.update({k: host_placement(v)
                               for k, v in pages.items()})

    def get(self, page_id):
        self.stats.lookups += 1
        if self.mode is MemoryMode.DEVMEM:
            return self._resident[page_id]
        if self.mode is MemoryMode.DC:
            if page_id in self._cache:
                self.stats.cache_hits += 1
                self._cache.move_to_end(page_id)
                return self._cache[page_id]
            self.stats.cache_misses += 1
        arr = device_placement(self._host[page_id])
        self.stats.host_to_device_bytes += self._page_bytes[page_id]
        if self.mode is MemoryMode.DC:
            self._cache[page_id] = arr
            while len(self._cache) > self._cache_pages:
                self._cache.popitem(last=False)
        return arr
