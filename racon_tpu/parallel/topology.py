"""Local device topology: enumerate chips, hand out executor slots.

The reference driver round-robins batches over every visible GPU from
one process (``src/cuda/cudapolisher.cpp:72-83``).  The TPU analog has
two shapes, and this module is where a run picks between them:

- **shard-per-chip** (the common case): each local device gets its own
  pinned engine pair and an in-process chip worker drains manifest
  shards onto it (``racon_tpu.exec.runner``), coordinated by the same
  lease files multi-process workers use — no collectives, no mesh, each
  chip runs the full single-device fast path (ragged packing, streaming
  sessions, SWAR) that a mesh run must disable;
- **mesh-sharded** (one contig dominates the plan): the existing
  ``sharded_align`` / ``sharded_refine_loop`` ``shard_map`` path splits
  that one shard's batches over all chips (``racon_tpu.parallel``).

Pinning rides plain JAX placement: a :class:`ChipSlot`'s :meth:`~
ChipSlot.pin` context makes ``jax.default_device`` the slot's device,
so the engines' host->device puts (and every computation that follows
them) land on that chip.  ``jax.default_device`` is thread-local, which
is exactly what lets N chip workers share one process.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass
from typing import List, Optional

from .. import flags


def local_devices() -> list:
    """Every device addressable by this process (``jax.local_devices()``
    — on multi-host jobs this is the host-local slice, which is the set
    one process can drive)."""
    import jax

    return list(jax.local_devices())


def n_local_chips() -> int:
    return len(local_devices())


def resolve_chips(requested: int = 0) -> int:
    """Number of in-process chip workers a run should spawn: an explicit
    request (CLI ``--chips``) wins, then ``RACON_TPU_CHIPS``, then every
    local device (floored at 1).  An explicit request for more chips
    than this process can address is an error, never a clamp — a
    ``--chips 4`` run on one device would be a 1-chip run that says
    nothing."""
    if requested <= 0:
        requested = flags.get_int("RACON_TPU_CHIPS")
    n = n_local_chips()
    if requested <= 0:
        return max(1, n)
    if requested > n:
        raise ValueError(
            f"--chips/RACON_TPU_CHIPS asks for {requested} chips but "
            f"only {n} local device(s) are visible")
    return requested


@dataclass
class ChipSlot:
    """One local chip's executor slot: the device plus its ordinal (the
    key per-device metrics, worker ids and plan assignments use)."""

    ordinal: int
    device: Optional[object] = None

    @property
    def key(self) -> str:
        return f"chip{self.ordinal}"

    def pin(self):
        """Context manager placing default JAX computation on this
        slot's device (thread-local; a no-op for the unpinned default
        slot, which keeps the single-chip path byte-for-byte the code
        it was before the scheduler existed)."""
        if self.device is None:
            return contextlib.nullcontext()
        import jax

        return jax.default_device(self.device)


class Topology:
    """The local chip set as executor slots.

    ``n_chips <= 1`` yields one *unpinned* slot — the legacy
    single-device path.  ``n_chips > 1`` yields one pinned slot per
    device prefix, slot 0 doubling as the mesh-capable slot (it may run
    plan shards marked mesh-sharded over ALL local chips)."""

    def __init__(self, n_chips: int = 0):
        n = resolve_chips(n_chips)
        if n <= 1:
            self.slots: List[ChipSlot] = [ChipSlot(0, None)]
        else:
            devs = local_devices()
            self.slots = [ChipSlot(k, devs[k]) for k in range(n)]

    @property
    def n_chips(self) -> int:
        return len(self.slots)

    def describe(self) -> dict:
        """Advisory topology record for plans/reports (platform +
        device kind + chip count)."""
        devs = local_devices()
        first = devs[0] if devs else None
        return {
            "n_chips": self.n_chips,
            "n_local_devices": len(devs),
            "platform": getattr(first, "platform", "unknown"),
            "device_kind": getattr(first, "device_kind", "unknown"),
        }
