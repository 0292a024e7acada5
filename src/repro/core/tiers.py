"""Storage-tier abstraction for the Sea data-placement hierarchy.

A *tier* is one level of the user-declared storage hierarchy (paper §3.1:
"Sea requires the user to specify at least two storage devices, a fast
temporary device used as cache and a slower long-term storage device").
Levels are ordered fastest-first; the last tier is the *base* (long-term,
persistent) tier — the Lustre/PFS analogue. A level may contain several
*roots* (e.g. 6 local SSDs): Sea selects among same-level roots by random
shuffle, mirroring the paper's metadata-server-free design.
"""

from __future__ import annotations

import os
import shutil
from dataclasses import dataclass

from .ledger import CapacityLedger, Reservation
from .shared_ledger import SharedCapacityLedger


@dataclass
class TierSpec:
    """Static description of one storage level.

    Bandwidths are used by the performance model / simulator and by
    benchmarks; placement itself only needs capacities.
    """

    name: str
    roots: tuple[str, ...]
    read_bw: float = 0.0          # bytes/s, 0 = unknown
    write_bw: float = 0.0         # bytes/s, 0 = unknown
    capacity: int | None = None   # per-root byte cap; None = ask the OS
    persistent: bool = False      # True only for the base (PFS) tier

    def __post_init__(self) -> None:
        if isinstance(self.roots, str):
            self.roots = (self.roots,)
        self.roots = tuple(os.path.abspath(r) for r in self.roots)
        if not self.roots:
            raise ValueError(f"tier {self.name!r} needs at least one root")


class Tier:
    """A live tier: spec + capacity accounting over its roots.

    ``used_bytes``/``free_bytes`` are O(1) lookups in the attached
    :class:`~repro.core.ledger.CapacityLedger`; the full ``os.walk``
    survives only as the ledger's reconcile path.
    """

    def __init__(
        self,
        spec: TierSpec,
        level: int,
        ledger: CapacityLedger | SharedCapacityLedger,
    ):
        self.spec = spec
        self.level = level
        self.ledger = ledger
        for root in spec.roots:
            os.makedirs(root, exist_ok=True)

    @property
    def name(self) -> str:
        return self.spec.name

    @property
    def roots(self) -> tuple[str, ...]:
        return self.spec.roots

    @property
    def persistent(self) -> bool:
        return self.spec.persistent

    # -- capacity ----------------------------------------------------------
    def used_bytes(self, root: str) -> int:
        """Bytes used under one root — an O(1) ledger lookup."""
        return self.ledger.used_bytes(root)

    def reserved_bytes(self, root: str) -> int:
        """In-flight write budget currently held against one root."""
        return self.ledger.reserved_bytes(root)

    def free_bytes(self, root: str) -> int:
        """Free bytes on one root, honouring the configured cap if any and
        discounting in-flight write reservations.

        The paper: "Sea queries all the available file systems directly to
        determine the amount of available space." — the ledger caches that
        query and reconciles against the file system periodically.
        """
        reserved = self.reserved_bytes(root)
        if self.spec.capacity is not None:
            return max(self.spec.capacity - self.used_bytes(root) - reserved, 0)
        try:
            st = os.statvfs(root)
            return max(st.f_bavail * st.f_frsize - reserved, 0)
        except OSError:
            return 0

    def total_free_bytes(self) -> int:
        return sum(self.free_bytes(r) for r in self.roots)

    def admissible(self, root: str, *, required: int, nbytes: int) -> bool:
        """Would a new ``nbytes`` write be admitted on this root?  Mirrors
        :meth:`CapacityLedger.try_reserve`: existing reservations count
        toward the ``required`` worst-case headroom rather than on top of
        it, so one in-flight writer does not disqualify a root that still
        provably fits another."""
        if self.spec.capacity is None:
            return self.free_bytes(root) >= required
        reserved = self.reserved_bytes(root)
        return self.spec.capacity - self.used_bytes(root) >= max(
            required, reserved + nbytes
        )

    # -- ledger notifications ----------------------------------------------
    def note_written(self, root: str, key: str, nbytes: int) -> None:
        self.ledger.note_written(root, key, nbytes)

    def note_removed(self, root: str, key: str) -> None:
        self.ledger.note_removed(root, key)

    def reserve_write(self, root: str, nbytes: int) -> Reservation:
        return self.ledger.reserve(root, nbytes)

    def commit_write(
        self, res: Reservation | None, root: str, key: str, nbytes: int
    ) -> None:
        if res is not None:
            self.ledger.commit(res, key, nbytes)
        else:
            self.ledger.note_written(root, key, nbytes)

    def release_write(self, res: Reservation | None) -> None:
        if res is not None:
            self.ledger.release(res)

    def reconcile(self) -> None:
        """On-demand reconciliation of every root of this tier."""
        for root in self.roots:
            self.ledger.reconcile(root)

    def root_of(self, path: str) -> str | None:
        """The root of this tier that ``path`` lives under, if any."""
        ap = os.path.abspath(path)
        for root in self.roots:
            if ap == root or ap.startswith(root + os.sep):
                return root
        return None

    def locate(self, relpath: str) -> str | None:
        """Return the real path of ``relpath`` if present on this tier."""
        for root in self.roots:
            p = os.path.join(root, relpath)
            if os.path.lexists(p):
                return p
        return None

    def wipe(self) -> None:
        for root in self.roots:
            if os.path.isdir(root):
                shutil.rmtree(root, ignore_errors=True)
            os.makedirs(root, exist_ok=True)
            self.ledger.forget(root)

    def __repr__(self) -> str:  # pragma: no cover
        return f"Tier(level={self.level}, name={self.name!r}, roots={self.roots})"


@dataclass
class Hierarchy:
    """Ordered collection of tiers, fastest (level 0) first. All tiers
    share one :class:`CapacityLedger` (sharded internally by root)."""

    tiers: list[Tier]
    ledger: CapacityLedger | SharedCapacityLedger

    @classmethod
    def from_specs(
        cls,
        specs: list[TierSpec],
        *,
        shared: bool = False,
        reconcile_interval_s: float = 5.0,
    ) -> "Hierarchy":
        if len(specs) < 2:
            raise ValueError(
                "Sea requires at least two storage devices: a fast cache "
                "tier and a slower long-term tier (paper §3.1)"
            )
        if not specs[-1].persistent:
            specs[-1].persistent = True  # last tier is the base by definition
        # shared: file-backed, fcntl-guarded accounting every process
        # mounting this hierarchy sees; default: in-process counters
        cls_ledger = SharedCapacityLedger if shared else CapacityLedger
        ledger = cls_ledger(reconcile_interval_s=reconcile_interval_s)
        return cls([Tier(s, i, ledger) for i, s in enumerate(specs)], ledger)

    def owner_of(self, path: str) -> tuple[Tier, str] | None:
        """The (tier, root) a real path lives under, if any."""
        for tier in self.tiers:
            root = tier.root_of(path)
            if root is not None:
                return tier, root
        return None

    def reconcile(self) -> None:
        """On-demand reconciliation of every root of every tier."""
        for tier in self.tiers:
            tier.reconcile()

    @property
    def base(self) -> Tier:
        """The long-term (persistent) tier — Lustre/PFS analogue."""
        return self.tiers[-1]

    @property
    def cache_tiers(self) -> list[Tier]:
        """All ephemeral tiers, fastest first."""
        return self.tiers[:-1]

    def __iter__(self):
        return iter(self.tiers)

    def __len__(self) -> int:
        return len(self.tiers)

    @property
    def total_roots(self) -> int:
        """Number of probe targets one full resolution cascade touches."""
        return sum(len(t.roots) for t in self.tiers)

    def locate(self, relpath: str) -> tuple[Tier, str] | None:
        """Find a file across the hierarchy, fastest tier first.

        This is the stateless resolution at the heart of Sea: no metadata
        server — a file's location IS its state on the file systems.
        (:class:`~repro.core.resolver.Resolver` caches this cascade; this
        method remains the source-of-truth fallback.)
        """
        for tier in self.tiers:
            real = tier.locate(relpath)
            if real is not None:
                return tier, real
        return None

    def locate_above(self, relpath: str, level: int) -> tuple[Tier, str] | None:
        """Find a replica on a tier *faster* than ``level`` — the
        write-side verify: an overwrite of a cached hit must never miss a
        faster copy (probes zero roots when ``level`` is already 0)."""
        for tier in self.tiers:
            if tier.level >= level:
                break
            real = tier.locate(relpath)
            if real is not None:
                return tier, real
        return None

    def locate_all(self, relpath: str) -> list[tuple[Tier, str]]:
        """Every replica of ``relpath`` across every root of every tier
        (``locate`` stops at the first hit per tier; removal must not)."""
        out: list[tuple[Tier, str]] = []
        for tier in self.tiers:
            for root in tier.roots:
                p = os.path.join(root, relpath)
                if os.path.lexists(p):
                    out.append((tier, p))
        return out
