"""Tests for the performance model (Eqs. 1–11) and the cluster simulator,
validated against the paper's reported results (§4)."""

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.model import (
    ClusterSpec,
    GiB,
    MiB,
    Workload,
    lustre_bounds,
    lustre_cached_makespan,
    lustre_makespan,
    lustre_read_bw,
    lustre_write_bw,
    sea_bounds,
    sea_cached_makespan,
    sea_flush_all_makespan,
    sea_makespan,
    sea_tier_volumes,
)
from repro.core.simulator import Simulator

PAPER = ClusterSpec()          # 5 nodes, 6 procs, 6 disks — paper defaults
W10 = Workload(B=1000, F=617 * MiB, n=10)
W5 = Workload(B=1000, F=617 * MiB, n=5)


# ------------------------------------------------------------------- model
def test_lustre_bw_eq2_eq3():
    # L = min(cN, sN, d_* min(d, cp)); with paper defaults cp=30 < d=44
    assert lustre_read_bw(PAPER) == PAPER.d_r * 30
    assert lustre_write_bw(PAPER) == PAPER.d_w * 30
    # many processes: OST count binds
    cl = PAPER.with_(p=64)
    assert lustre_write_bw(cl) == PAPER.d_w * 44
    # tiny cluster: network binds
    cl = PAPER.with_(N=10 * MiB, c=1, p=64)
    assert lustre_write_bw(cl) == 10 * MiB


def test_workload_volumes():
    assert W10.D_I == 1000 * 617 * MiB
    assert W10.D_m == 9 * 1000 * 617 * MiB
    assert W10.D_f == 1000 * 617 * MiB


def test_sea_tier_volumes_conservation():
    """Spill volumes partition the written/read bytes exactly (Eqs. 8–10)."""
    v = sea_tier_volumes(W10, PAPER)
    assert v["D_tw"] + v["D_gw"] + v["D_Lw"] == pytest.approx(W10.D_m + W10.D_f)
    assert v["D_tr"] + v["D_gr"] + v["D_Lr"] == pytest.approx(W10.D_m)
    assert all(val >= 0 for val in v.values())


def test_bounds_ordering():
    lo_l, hi_l = lustre_bounds(W10, PAPER)
    lo_s, hi_s = sea_bounds(W10, PAPER)
    assert lo_l <= hi_l and lo_s <= hi_s
    # Sea and Lustre share an identical lower bound (paper §3.4)
    assert lo_l == pytest.approx(lo_s)
    # In the data-intensive regime Sea's upper bound beats Lustre's
    assert hi_s < hi_l


def test_flush_all_costs_more():
    assert sea_flush_all_makespan(W10, PAPER) > sea_makespan(W10, PAPER)


@settings(max_examples=50, deadline=None)
@given(
    c=st.integers(1, 16),
    p=st.integers(1, 64),
    g=st.integers(1, 8),
    n=st.integers(1, 20),
)
def test_model_positive_and_monotone_in_data(c, p, g, n):
    cl = ClusterSpec(c=c, p=p, g=g)
    w = Workload(B=100, F=64 * MiB, n=n)
    w2 = Workload(B=200, F=64 * MiB, n=n)
    for fn in (lustre_makespan, lustre_cached_makespan, sea_makespan,
               sea_cached_makespan):
        assert fn(w, cl) > 0
        assert fn(w2, cl) >= fn(w, cl)  # more data never finishes earlier


@settings(max_examples=30, deadline=None)
@given(c=st.integers(1, 8), p=st.integers(1, 16), n=st.integers(2, 12))
def test_cached_bound_below_uncached(c, p, n):
    cl = ClusterSpec(c=c, p=p)
    w = Workload(B=200, F=256 * MiB, n=n)
    assert lustre_cached_makespan(w, cl) <= lustre_makespan(w, cl) * 1.0001
    assert sea_cached_makespan(w, cl) <= sea_makespan(w, cl) * 1.0001


# --------------------------------------------------------------- simulator
@pytest.fixture(scope="module")
def base_sims():
    rl = Simulator(PAPER, W10, "lustre").run()
    rs = Simulator(PAPER, W10, "sea").run()
    return rl, rs


def test_sim_base_speedup_matches_paper(base_sims):
    """Paper §4.1: 2.4x speedup at the fixed condition (5 nodes, 6 procs,
    6 disks, 10 iterations)."""
    rl, rs = base_sims
    speedup = rl.makespan / rs.makespan
    assert 2.0 <= speedup <= 2.9, speedup


def test_sim_within_model_bounds(base_sims):
    """The paper's validity criterion: measurements fall within the model's
    [cached, uncached] bounds at the base condition."""
    rl, rs = base_sims
    lo, hi = lustre_bounds(W10, PAPER)
    assert lo * 0.95 <= rl.makespan <= hi * 1.05
    lo, hi = sea_bounds(W10, PAPER)
    assert lo * 0.95 <= rs.makespan <= hi * 1.10


def test_sim_single_node_parity():
    """Paper §4.1: 'Sea at a single node likely performs equivalently to
    Lustre'."""
    cl = PAPER.with_(c=1)
    rl = Simulator(cl, W10, "lustre").run()
    rs = Simulator(cl, W10, "sea").run()
    assert 0.85 <= rl.makespan / rs.makespan <= 1.2


def test_sim_single_iteration_no_speedup():
    """Paper §4.1: 'Sea at a single iteration can at best perform similarly
    or slightly worse than Lustre' (no intermediate data)."""
    w = Workload(B=1000, F=617 * MiB, n=1)
    rl = Simulator(PAPER, w, "lustre").run()
    rs = Simulator(PAPER, w, "sea").run()
    assert rl.makespan / rs.makespan <= 1.35


def test_sim_single_disk_slowdown():
    """Paper §4.1 (Fig. 2b): Sea underperforms Lustre with one local disk."""
    cl = PAPER.with_(g=1)
    rl = Simulator(cl, W5, "lustre").run()
    rs = Simulator(cl, W5, "sea").run()
    assert rl.makespan / rs.makespan < 1.0


def test_sim_more_disks_more_speedup():
    """Paper §4.1 (Fig. 2b): ~2x speedup by 6 disks, monotone trend."""
    speedups = []
    for g in (1, 4, 6):
        cl = PAPER.with_(g=g)
        rl = Simulator(cl, W5, "lustre").run()
        rs = Simulator(cl, W5, "sea").run()
        speedups.append(rl.makespan / rs.makespan)
    assert speedups == sorted(speedups)
    assert speedups[-1] >= 1.9


def test_sim_process_scaling_peak_speedup():
    """Paper §4.1 (Fig. 2d): largest speedup ~3x in the 16–32 process
    range."""
    best = 0.0
    for p in (16, 32):
        cl = PAPER.with_(p=p)
        rl = Simulator(cl, W5, "lustre").run()
        rs = Simulator(cl, W5, "sea").run()
        best = max(best, rl.makespan / rs.makespan)
    assert best >= 2.5


def test_sim_exp4_lustre_exceeds_model_bounds():
    """Paper §4.2: at 30+ processes Lustre 'declined above model bounds' —
    the simulator reproduces the bound violation."""
    cl = PAPER.with_(p=32)
    rl = Simulator(cl, W5, "lustre").run()
    _lo, hi = lustre_bounds(W5, cl)
    assert rl.makespan > hi


def test_sim_fig3_flush_all_ratios():
    """Paper §4.3 (Fig. 3): flush-all 3.5x slower than in-memory and 1.3x
    slower than Lustre (5 nodes, 64 procs, 6 disks, 5 iters)."""
    cl = PAPER.with_(p=64)
    rl = Simulator(cl, W5, "lustre").run()
    rs = Simulator(cl, W5, "sea").run()
    rf = Simulator(cl, W5, "sea-flushall").run()
    assert 2.8 <= rf.makespan / rs.makespan <= 4.2
    assert 1.1 <= rf.makespan / rl.makespan <= 1.5


def test_sim_conservation_of_bytes():
    rs = Simulator(PAPER, W5, "sea").run()
    app_bytes = sum(
        v for k, v in rs.bytes_by_tier.items() if k != "flush"
    )
    assert app_bytes == pytest.approx(W5.D_m + W5.D_f, rel=1e-6)
    # in-memory mode flushes exactly the final outputs
    assert rs.bytes_by_tier["flush"] == pytest.approx(W5.D_f, rel=1e-6)


def test_sim_compute_masks_flush_overhead():
    """Paper §5.5: flush-all overheads are masked when compute dominates."""
    cl = PAPER.with_(p=4)
    w = Workload(B=100, F=617 * MiB, n=5)
    slow = dict(compute_s_per_iter=30.0)
    rs = Simulator(cl, w, "sea", **slow).run()
    rf = Simulator(cl, w, "sea-flushall", **slow).run()
    assert rf.makespan / rs.makespan < 1.3  # overhead mostly hidden


def test_sim_beyond_paper_eviction_helps_when_tmpfs_small():
    """Beyond-paper: evicting consumed intermediates lets tmpfs absorb more
    writes when capacity is scarce."""
    cl = PAPER.with_(t=8 * GiB)
    w = Workload(B=200, F=617 * MiB, n=10)
    r0 = Simulator(cl, w, "sea").run()
    r1 = Simulator(cl, w, "sea", evict_intermediates=True).run()
    assert r1.makespan <= r0.makespan * 1.001
