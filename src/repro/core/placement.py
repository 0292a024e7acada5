"""Tier-selection policy (paper §3.1.2).

"Sea will then go through the hierarchy of available storage devices and
select the fastest storage device with sufficient available space."

Eligibility: a root is eligible if ``free >= n_procs * max_file_size`` —
Sea cannot predict output sizes, so it reserves worst-case room for every
concurrent writer ("the number of threads multiplied by the file size does
not exceed storage space"). Same-level roots are picked by random shuffle:
no metadata server, no locking — decentralization over optimal packing.

``free`` is an O(1) capacity-ledger lookup and additionally discounts
*in-flight write reservations*: each open-for-write holds a
``max_file_size`` budget against its root (:meth:`reserve_write`) until
the close commits the actual size. This
tracks the ``n_procs * max_file_size`` headroom per-root as writes happen,
instead of re-deriving it from a filesystem walk on every call.
"""

from __future__ import annotations

import random

from .ledger import Reservation
from .tiers import Hierarchy, Tier


class PlacementPolicy:
    def __init__(
        self,
        hierarchy: Hierarchy,
        *,
        max_file_size: int,
        n_procs: int,
        rng: random.Random | None = None,
        health=None,
    ):
        self.hierarchy = hierarchy
        self.max_file_size = max_file_size
        self.n_procs = n_procs
        self.rng = rng or random.Random()
        #: HealthTracker (bound by SeaFS): quarantined cache roots are
        #: excluded from selection until their breaker re-admits them —
        #: the base tier is never filtered (unconditional fallback)
        self.health = health

    def _root_allowed(self, tier: Tier, root: str) -> bool:
        """Side-effect-free eligibility filter: never claims the breaker's
        half-open probe slot (enumeration must not starve re-admission —
        see :meth:`HealthTracker.admissible`)."""
        if self.health is None or tier.spec.persistent:
            return True
        return self.health.admissible(root)

    def claim_root(self, tier: Tier, root: str) -> bool:
        """Claim `root` for I/O that is actually about to happen: a closed
        breaker is a free pass; a re-admitting breaker hands this caller
        the single half-open probe slot (False = someone else holds it,
        re-select). Call only at the point a root is *chosen*, never while
        merely enumerating candidates."""
        if self.health is None or tier.spec.persistent:
            return True
        return self.health.allow(root)

    @property
    def required_bytes(self) -> int:
        return self.max_file_size * self.n_procs

    def eligible_roots(self, tier: Tier) -> list[str]:
        roots = list(tier.roots)
        self.rng.shuffle(roots)  # paper: "selected by Sea via a random shuffling"
        return [
            r
            for r in roots
            if self._root_allowed(tier, r)
            and tier.admissible(
                r, required=self.required_bytes, nbytes=self.max_file_size
            )
        ]

    def select(self) -> tuple[Tier, str]:
        """Fastest tier/root with sufficient space; the base tier is the
        unconditional fallback (there is nowhere slower to go)."""
        for tier in self.hierarchy.cache_tiers:
            roots = self.eligible_roots(tier)
            if roots:
                return tier, roots[0]
        base = self.hierarchy.base
        roots = self.eligible_roots(base)
        return base, roots[0] if roots else base.roots[0]

    def place_new(
        self, *, reserve: bool, make_room=None
    ) -> tuple[Tier, str, Reservation | None]:
        """Full placement of a *new* file: select the fastest eligible
        root and (optionally) atomically admit the write against it.

        ``make_room`` (LRU eviction hook) is consulted whenever selection
        falls through to the base tier while cache tiers exist: if it
        frees space, selection re-runs. A lost admission race re-selects
        (up to 8 attempts) so concurrent writers of different keys can
        never jointly over-commit a capped root; the base tier is the
        unconditional fallback.
        """
        for _attempt in range(8):
            tier, root = self.select()
            if (
                make_room is not None
                and tier is self.hierarchy.base
                and self.hierarchy.cache_tiers
            ):
                if make_room():
                    tier, root = self.select()
            if not reserve:
                if tier is self.hierarchy.base or self.claim_root(tier, root):
                    return tier, root, None
                continue  # lost the half-open probe slot: re-select
            if tier is self.hierarchy.base:
                # unconditional fallback: there is nowhere slower to go
                return tier, root, self.reserve_write(tier, root)
            admitted, res = self.acquire_write(tier, root)
            if admitted:
                # the root is definitely getting this write: claim the
                # breaker probe slot last, so a lost admission race never
                # burns the probe without I/O happening
                if self.claim_root(tier, root):
                    return tier, root, res
                self.release_write(tier, res)
        tier = self.hierarchy.base
        root = tier.roots[0]
        return tier, root, self.reserve_write(tier, root)

    # -- in-flight write budgets (ledger-backed) ----------------------------
    def reserve_write(self, tier: Tier, root: str) -> Reservation:
        """Hold a worst-case (``max_file_size``) budget for one in-flight
        write so concurrent writers cannot collectively over-commit a root
        whose bytes have not reached the disk yet."""
        return tier.reserve_write(root, self.max_file_size)

    def acquire_write(
        self, tier: Tier, root: str
    ) -> tuple[bool, Reservation | None]:
        """Admission for a *new* file on a selected root: atomically
        re-check eligibility and reserve. Returns (admitted, reservation).
        Capped roots use the ledger's single-critical-section check; on a
        lost race the caller re-selects. Uncapped roots (statvfs-backed)
        cannot meaningfully over-commit at this scale, so they reserve
        unconditionally."""
        if tier.spec.capacity is None:
            return True, tier.reserve_write(root, self.max_file_size)
        res = tier.ledger.try_reserve(
            root,
            self.max_file_size,
            capacity=tier.spec.capacity,
            required=self.required_bytes,
        )
        return (res is not None), res

    def commit_write(
        self, tier: Tier, res: Reservation | None, root: str, key: str, nbytes: int
    ) -> None:
        """Write finished: swap the reservation for the actual file size."""
        tier.commit_write(res, root, key, nbytes)

    def release_write(self, tier: Tier, res: Reservation | None) -> None:
        """Write abandoned: return the budget untouched."""
        tier.release_write(res)

    def select_cache_for_prefetch(self, nbytes: int) -> tuple[Tier, str] | None:
        """Fastest cache root that can hold ``nbytes`` (prefetch staging)."""
        for tier in self.hierarchy.cache_tiers:
            roots = list(tier.roots)
            self.rng.shuffle(roots)
            for r in roots:
                if (
                    self._root_allowed(tier, r)
                    and tier.free_bytes(r) >= max(nbytes, self.required_bytes)
                    and self.claim_root(tier, r)  # chosen: claim the probe
                ):
                    return tier, r
        return None
