"""Sea configuration.

Mirrors the paper's configuration surface (§3.1.1/§5.1): a mountpoint, an
ordered storage hierarchy, the maximum file size the workflow produces, the
number of concurrent processes, and the three list files
(.sea_flushlist / .sea_evictlist / .sea_prefetchlist).

"At minimum, Sea requires the specification of a configuration file for it
to work." — we accept a Python dataclass, a TOML/INI-style file, or
environment variables, keeping the minimal-configuration requirement.
"""

from __future__ import annotations

import configparser
import os
from dataclasses import dataclass, field, fields, replace

from .tiers import Hierarchy, TierSpec

#: default basenames, identical to the paper
FLUSHLIST_NAME = ".sea_flushlist"
EVICTLIST_NAME = ".sea_evictlist"
PREFETCHLIST_NAME = ".sea_prefetchlist"


def _read_scalars(
    section: configparser.SectionProxy, cls: type, skip: tuple[str, ...] = ()
) -> dict:
    """Keyword arguments for dataclass ``cls`` from the keys of one INI
    section that name its scalar fields, each parsed by the field's
    declared type (annotations are strings under ``from __future__ import
    annotations``). An absent key passes nothing, so the dataclass holds
    the only copy of every default; unknown keys are ignored."""
    getters = {
        "bool": section.getboolean,
        "int": section.getint,
        "int | None": section.getint,
        "float": section.getfloat,
        "str": section.get,
    }
    return {
        f.name: getters[f.type](f.name)
        for f in fields(cls)
        if f.type in getters and f.name not in skip and f.name in section
    }


@dataclass
class SeaConfig:
    mount: str                          # virtual mountpoint the app writes under
    tiers: list[TierSpec]               # fastest first; last = persistent base
    max_file_size: int = 1 << 20        # F: max bytes one workflow file may have
    n_procs: int = 1                    # p: concurrent writer processes
    flushlist: tuple[str, ...] = ()     # glob patterns, relative to mount
    evictlist: tuple[str, ...] = ()
    prefetchlist: tuple[str, ...] = ()
    #: flusher behaviour
    flush_interval_s: float = 0.05      # poll period of the flush-and-evict daemon
    max_inflight_flush_bytes: int = 1 << 30  # beyond-paper: bounded async flushing
    flush_workers: int = 2              # worker pool size: flushes of independent
                                        # keys proceed concurrently
    #: capacity-accounting ledger (O(1) placement hot path)
    ledger_reconcile_interval_s: float = 5.0  # staleness bound for absorbing
                                              # external writers via re-walk
    #: namespace resolver (O(1) resolution hot path, verify-on-hit)
    resolver_negative_ttl_s: float = 0.05  # how long a confirmed miss is
                                           # trusted (read-miss storms)
    resolver_verify_window_s: float = 0.05  # how long a verified hit skips
                                            # the lstat (0 = verify every
                                            # hit; data reads always heal
                                            # on ENOENT either way)
    #: data plane (chunked streaming transfer engine — every byte moved
    #: between tiers goes through repro.core.transfer.TransferEngine)
    transfer_workers: int = 4           # bounded parallel transfer pool size
    transfer_chunk_bytes: int = 32 << 20  # chunk size of the streaming copy
                                        # loop (zero-copy syscalls: large
                                        # chunks cost no userspace memory,
                                        # small ones measurably lose to the
                                        # per-call setup overhead)
    transfer_bandwidth_caps: dict[str, float] = field(default_factory=dict)
                                        # bytes/sec per tier pair: "src->dst",
                                        # "src->*", "*->dst", or "*" wildcard
    transfer_retries: int = 2           # retry-with-backoff on transient I/O
    transfer_backoff_s: float = 0.02    # first backoff; doubles per attempt
    transfer_deadline_s: float = 0.0    # >0: abort a copy whose chunk loop
                                        # makes no progress for this long —
                                        # the reservation is released and the
                                        # root's breaker trips (0 = disabled)
    #: failure domains (per-root health tracking + circuit breakers)
    health_window_s: float = 30.0       # sliding window the per-root error
                                        # rate is computed over
    health_error_threshold: float = 0.5  # error rate (within the window) that
                                         # opens a cache root's breaker
    health_min_events: int = 4          # minimum events in the window before
                                        # the error rate can trip the breaker
    health_open_s: float = 2.0          # how long an open breaker waits
                                        # before admitting a half-open probe
    #: fault injection (chaos testing; empty = plane inactive)
    faults: str = ""                    # injection spec, e.g.
                                        # "transfer.chunk:errno=EIO,p=0.5,n=3"
                                        # (see repro.core.faults for grammar)
    fault_seed: int = 0                 # seed of the injection schedule RNG
                                        # (print it: reruns are reproducible)
    #: multi-process coordination (n_procs Sea instances on one node)
    shared_ledger: bool = False         # file-backed cross-process ledger under
                                        # each root + single-flusher election
    leader_heartbeat_s: float = 0.5     # flush-leader heartbeat period; follower
                                        # takeover within 2 missed heartbeats
    #: cluster-scale cache federation (peer-aware miss resolution:
    #: local hit -> peer hit -> base fallback; registry on the base tier)
    federation: bool = False            # publish cache replicas to the shared
                                        # key-location registry and pull
                                        # peer->cache on a local miss
                                        # (requires shared_ledger=True)
    federation_node: str = ""           # this node's registry identity
                                        # ("" = "<host>-<pid>")
    federation_heartbeat_s: float = 1.0  # membership heartbeat period
    federation_node_ttl_s: float = 10.0  # cross-host liveness window: a node
                                         # whose heartbeat is older is dead
                                         # and its entries expire on reconcile
                                         # (same-host death is caught
                                         # immediately by the PID probe)
    #: adaptive read path (predictive readahead + open fast path)
    readahead: bool = False             # access-pattern-driven speculative
                                        # staging base->cache (beyond-paper)
    readahead_depth: int = 4            # max files staged ahead per detected
                                        # sequence (adaptive, 1..depth)
    readahead_min_confidence: float = 0.5  # empirical confidence a predicted
                                           # key needs before staging
    #: extent-granular data plane (block-level placement on cache tiers)
    extent_map: bool = False            # True = key -> extent map on cache
                                        # tiers: sparse partial replicas,
                                        # streaming reads through partially
                                        # staged files, per-extent eviction
                                        # (False = whole-file plane, the
                                        # PR-5 behaviour)
    extent_bytes: int = 32 << 20        # fixed extent (block) size of the
                                        # extent map; staging, admission,
                                        # readahead and eviction all operate
                                        # at this granularity
    #: training I/O (async checkpoint writer + device-feed pipeline)
    checkpoint_async: bool = True       # training drivers overlap checkpoint
                                        # writes with compute (save() itself
                                        # defaults to blocking; this knob is
                                        # what launch/train passes through)
    checkpoint_workers: int = 2         # per-save cap on concurrent leaf
                                        # writes fanned through the shared
                                        # TransferEngine worker pool
    device_prefetch: int = 2            # device batches held in flight by
                                        # DataPipeline.device_iter (host ->
                                        # device double buffering; 1 = no
                                        # overlap beyond the current batch)
    #: beyond-paper options (all default OFF for paper faithfulness)
    stripe_chunk_bytes: int = 0         # >0 enables striping across same-level roots
    lru_evict: bool = False             # auto-evict LRU when a tier is full

    def __post_init__(self) -> None:
        self.mount = os.path.abspath(self.mount)
        self.flushlist = tuple(self.flushlist)
        self.evictlist = tuple(self.evictlist)
        self.prefetchlist = tuple(self.prefetchlist)
        if self.max_file_size <= 0:
            raise ValueError("max_file_size must be positive")
        if self.n_procs <= 0:
            raise ValueError("n_procs must be positive")
        if self.flush_workers <= 0:
            raise ValueError("flush_workers must be positive")
        if self.ledger_reconcile_interval_s < 0:
            raise ValueError("ledger_reconcile_interval_s must be >= 0")
        if self.resolver_negative_ttl_s < 0:
            raise ValueError("resolver_negative_ttl_s must be >= 0")
        if self.resolver_verify_window_s < 0:
            raise ValueError("resolver_verify_window_s must be >= 0")
        if self.leader_heartbeat_s <= 0:
            raise ValueError("leader_heartbeat_s must be positive")
        if self.transfer_workers <= 0:
            raise ValueError("transfer_workers must be positive")
        if self.transfer_chunk_bytes <= 0:
            raise ValueError("transfer_chunk_bytes must be positive")
        if self.transfer_retries < 0:
            raise ValueError("transfer_retries must be >= 0")
        if self.transfer_backoff_s < 0:
            raise ValueError("transfer_backoff_s must be >= 0")
        if self.transfer_deadline_s < 0:
            raise ValueError("transfer_deadline_s must be >= 0")
        if self.health_window_s <= 0:
            raise ValueError("health_window_s must be positive")
        if not 0.0 < self.health_error_threshold <= 1.0:
            raise ValueError("health_error_threshold must be in (0, 1]")
        if self.health_min_events <= 0:
            raise ValueError("health_min_events must be positive")
        if self.health_open_s <= 0:
            raise ValueError("health_open_s must be positive")
        self.transfer_bandwidth_caps = dict(self.transfer_bandwidth_caps)
        for pair, rate in self.transfer_bandwidth_caps.items():
            if float(rate) <= 0:
                raise ValueError(
                    f"transfer_bandwidth_caps[{pair!r}] must be positive"
                )
        if self.readahead_depth <= 0:
            raise ValueError("readahead_depth must be positive")
        if not 0.0 <= self.readahead_min_confidence <= 1.0:
            raise ValueError("readahead_min_confidence must be in [0, 1]")
        if self.extent_bytes <= 0:
            raise ValueError("extent_bytes must be positive")
        if self.federation and not self.shared_ledger:
            raise ValueError("federation requires shared_ledger=True")
        if self.federation_heartbeat_s <= 0:
            raise ValueError("federation_heartbeat_s must be positive")
        if self.federation_node_ttl_s <= self.federation_heartbeat_s:
            raise ValueError(
                "federation_node_ttl_s must exceed federation_heartbeat_s"
            )
        if self.checkpoint_workers <= 0:
            raise ValueError("checkpoint_workers must be positive")
        if self.device_prefetch <= 0:
            raise ValueError("device_prefetch must be positive")

    # -- presets (paper §3.1.1: "two main modes based on flushing spec") ----
    def in_memory(self, final_globs: tuple[str, ...]) -> "SeaConfig":
        """In-memory computing: only final outputs are flushed (and evicted
        once flushed); intermediates never touch the base tier."""
        return replace(self, flushlist=tuple(final_globs), evictlist=tuple(final_globs))

    def copy_all(self) -> "SeaConfig":
        """Copy-all: everything is materialized to long-term storage."""
        return replace(self, flushlist=("*",), evictlist=())

    def build_hierarchy(self) -> Hierarchy:
        return Hierarchy.from_specs(
            list(self.tiers),
            shared=self.shared_ledger,
            reconcile_interval_s=self.ledger_reconcile_interval_s,
        )

    # -- parsing -------------------------------------------------------------
    @classmethod
    def from_file(cls, path: str) -> "SeaConfig":
        """Parse an INI-style Sea configuration file::

            [sea]
            mount = /sea
            max_file_size = 647088128
            n_procs = 6

            [tier.tmpfs]
            roots = /dev/shm/sea
            write_bw = 2684354560
            read_bw = 7000000000

            [tier.pfs]
            roots = /lustre/scratch
            persistent = true
        """
        cp = configparser.ConfigParser()
        with open(path) as f:
            cp.read_file(f)
        # [transfer.caps] keys are tier-pair names ("NVMe->pfs") that must
        # match TierSpec names exactly — re-read just that section with a
        # case-preserving transform so every other section keeps the
        # historical case-insensitive option lookup
        caps: dict[str, float] = {}
        if cp.has_section("transfer.caps"):
            cpc = configparser.ConfigParser()
            cpc.optionxform = str
            with open(path) as f:
                cpc.read_file(f)
            caps = {
                k: cpc["transfer.caps"].getfloat(k)
                for k in cpc.options("transfer.caps")
                if k not in cpc.defaults()  # [DEFAULT] keys are not caps
            }
        sea = cp["sea"]
        tiers: list[TierSpec] = []
        for section in cp.sections():
            if not section.startswith("tier."):
                continue
            t = cp[section]
            tiers.append(
                TierSpec(
                    name=section[len("tier.") :],
                    roots=tuple(x.strip() for x in t["roots"].split(",")),
                    **_read_scalars(t, TierSpec, skip=("name",)),
                )
            )
        base = os.path.dirname(os.path.abspath(path))

        def _read_list(name: str) -> tuple[str, ...]:
            p = os.path.join(base, name)
            if not os.path.exists(p):
                return ()
            with open(p) as f:
                return tuple(
                    ln.strip() for ln in f if ln.strip() and not ln.startswith("#")
                )

        return cls(
            mount=sea["mount"],
            tiers=tiers,
            transfer_bandwidth_caps=caps,
            flushlist=_read_list(FLUSHLIST_NAME),
            evictlist=_read_list(EVICTLIST_NAME),
            prefetchlist=_read_list(PREFETCHLIST_NAME),
            **_read_scalars(sea, cls, skip=("mount",)),
        )

    @classmethod
    def from_env(cls, env: dict[str, str] | None = None) -> "SeaConfig":
        """SEA_CONFIG=<path> or SEA_MOUNT/SEA_TIERS=<root:root:...>."""
        env = dict(os.environ if env is None else env)
        if "SEA_CONFIG" in env:
            return cls.from_file(env["SEA_CONFIG"])
        roots = [r for r in env.get("SEA_TIERS", "").split(":") if r]
        if len(roots) < 2:
            raise ValueError("SEA_TIERS must list >=2 roots (fastest first)")
        tiers = [TierSpec(name=f"t{i}", roots=(r,)) for i, r in enumerate(roots)]
        tiers[-1] = replace(tiers[-1], persistent=True)
        return cls(
            mount=env.get("SEA_MOUNT", "/sea"),
            tiers=tiers,
            max_file_size=int(env.get("SEA_MAX_FILE_SIZE", 1 << 20)),
            n_procs=int(env.get("SEA_NPROCS", "1")),
            shared_ledger=env.get("SEA_SHARED_LEDGER", "0") not in ("0", "", "false"),
            federation=env.get("SEA_FEDERATION", "0") not in ("0", "", "false"),
            federation_node=env.get("SEA_FEDERATION_NODE", ""),
            readahead=env.get("SEA_READAHEAD", "0") not in ("0", "", "false"),
            extent_map=env.get("SEA_EXTENT_MAP", "0") not in ("0", "", "false"),
            extent_bytes=int(env.get("SEA_EXTENT_BYTES", 32 << 20)),
            transfer_deadline_s=float(env.get("SEA_TRANSFER_DEADLINE_S", "0")),
            faults=env.get("SEA_FAULTS", ""),
            fault_seed=int(env.get("SEA_FAULT_SEED", "0")),
        )


def default_local_config(
    workdir: str,
    *,
    max_file_size: int = 1 << 20,
    n_procs: int = 1,
    tmpfs_capacity: int | None = None,
    disk_capacity: int | None = None,
    n_disks: int = 1,
) -> SeaConfig:
    """A convenient single-node hierarchy rooted under ``workdir``:
    tmpfs (/dev/shm) -> local disk -> 'pfs' directory (base tier).

    Used by tests, examples, and the framework's checkpoint/data layers.
    """
    shm = "/dev/shm" if os.path.isdir("/dev/shm") else workdir
    # namespace the tmpfs root by the FULL workdir path (hashed) — basename
    # collisions across runs must never share a burst buffer
    import hashlib

    tag = hashlib.sha1(os.path.abspath(workdir).encode()).hexdigest()[:12]
    return SeaConfig(
        mount=os.path.join(workdir, "mount"),
        tiers=[
            TierSpec(
                name="tmpfs",
                roots=(os.path.join(shm, f"sea_{tag}"),),
                capacity=tmpfs_capacity,
                read_bw=7.0e9,
                write_bw=2.7e9,
            ),
            TierSpec(
                name="disk",
                roots=tuple(
                    os.path.join(workdir, f"disk{i}") for i in range(n_disks)
                ),
                capacity=disk_capacity,
                read_bw=5.26e8,
                write_bw=4.47e8,
            ),
            TierSpec(
                name="pfs",
                roots=(os.path.join(workdir, "pfs"),),
                read_bw=1.45e9,
                write_bw=1.27e8,
                persistent=True,
            ),
        ],
        max_file_size=max_file_size,
        n_procs=n_procs,
    )
