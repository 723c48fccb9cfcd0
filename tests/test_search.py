"""The two-stage scheme search: local (3.3.1), global DP/PBQP (3.3.2).

The deterministic (stub-measured) guided-search and database
round-trip/forward-compat tests live in ``test_guided_search_db.py`` so
they run even without hypothesis installed.
"""
import numpy as np
import pytest
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import global_search, pbqp
from repro.core.cost import conv_vmem_bytes, fits_vmem
from repro.core.local_search import (ScheduleDatabase, local_search,
                                     roofline_runner)
from repro.core.schedule import ConvSchedule, ConvWorkload, candidate_schedules

WL = ConvWorkload(batch=1, in_channels=64, out_channels=64, height=28,
                  width=28, kh=3, kw=3, stride=1, pad=1)


# ---------------------------------------------------------------------------
# Local search
# ---------------------------------------------------------------------------

def test_candidates_all_legal():
    for s in candidate_schedules(WL):
        s.validate(WL)     # raises on an illegal tuple


def test_local_search_ranked():
    res = local_search(WL)
    costs = [r.cost_s for r in res.ranked]
    assert costs == sorted(costs)
    assert res.best_for_layout(res.best.ic_bn, res.best.oc_bn).schedule \
        == res.best


def test_vmem_footprint_counts_lane_padding():
    """An RGB stem block (ic_bn=3) pads its 3 channels to the 128 lanes:
    the footprint is that of a 128-channel plane, not a 3-channel one."""
    stem = ConvWorkload(batch=1, in_channels=3, out_channels=64, height=224,
                        width=224, kh=7, kw=7, stride=2, pad=3)
    s = ConvSchedule(3, 64, 2, 112, True, "tap_stack")
    # (230 rows, 2 column phases, 115 -> 120 sublanes, 3 -> 128 lanes) fp32,
    # single-buffered: the only channel chunk
    assert conv_vmem_bytes(stem, s) >= 230 * 2 * 120 * 128 * 4
    assert fits_vmem(stem, s)


def test_local_search_ranks_only_schedules_that_fit_vmem():
    # the 300x300 stem of ssd-resnet-50: for the Pallas kernel, without its
    # pool only the schedules that fit are ranked; with its 3x3/2 max-pool
    # fused none fits, which is an error.  The jnp templates stage nothing
    # in the kernel's VMEM: their search ranks every candidate
    stem = dict(batch=1, in_channels=3, out_channels=64, height=300,
                width=300, kh=7, kw=7, stride=2, pad=3)
    wl = ConvWorkload(**stem)
    space = candidate_schedules(wl, pallas=True)
    fitting = [s for s in space if fits_vmem(wl, s)]
    assert fitting and len(fitting) < len(space)
    ranked = {r.schedule for r in local_search(wl, pallas=True).ranked}
    assert ranked == set(fitting)
    assert len(local_search(wl).ranked) == len(candidate_schedules(wl))
    big = ConvWorkload(**stem, fused_bn=True, fused_relu=True,
                       fused_pool="max", pool_k=3, pool_stride=2, pool_pad=1)
    assert not any(fits_vmem(big, s)
                   for s in candidate_schedules(big, pallas=True))
    with pytest.raises(ValueError, match="VMEM budget"):
        local_search(big, pallas=True)
    assert len(local_search(big).ranked) == len(candidate_schedules(big))


def test_pallas_space_is_the_kernels():
    """The Pallas kernel's M-tile is the whole output row and it runs one
    GEMM per tap: its space has no ow_bn or variant axis, and the database
    keys its rankings apart from the jnp templates'."""
    _, ow = WL.out_hw
    space = candidate_schedules(WL, pallas=True)
    assert {(s.ow_bn, s.variant) for s in space} == {(ow, "per_tap")}
    assert {(s.ic_bn, s.oc_bn) for s in space} == {
        (s.ic_bn, s.oc_bn) for s in candidate_schedules(WL)}
    db = ScheduleDatabase()
    jnp_best, pallas_best = db.search(WL).best, db.search(WL, pallas=True).best
    assert len(db) == 2 and pallas_best in space
    assert db.search(WL).best == jnp_best


def test_schedule_database_roundtrip(tmp_path):
    db = ScheduleDatabase(tmp_path / "db.json")
    r1 = db.search(WL)
    assert len(db) == 1
    db2 = ScheduleDatabase(tmp_path / "db.json")   # reload from disk
    r2 = db2.search(WL)
    assert [x.schedule for x in r1.ranked] == [x.schedule for x in r2.ranked]


def test_database_memoizes():
    db = ScheduleDatabase()
    calls = []

    def runner(wl, s):
        calls.append(1)
        return roofline_runner(wl, s)

    db.search(WL, runner=runner)
    n1 = len(calls)
    db.search(WL, runner=runner)    # same workload: no new evaluations
    assert len(calls) == n1


# ---------------------------------------------------------------------------
# Global search: DP exactness, PBQP quality (paper: >= 88% of optimum)
# ---------------------------------------------------------------------------

def _random_problem(seed, n_lo=2, n_hi=7):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(n_lo, n_hi))
    topo = [f"n{i}" for i in range(n)]
    nc = {m: rng.uniform(0, 10, size=int(rng.integers(2, 4))) for m in topo}
    ec = {}
    for i in range(n):
        for j in range(i + 1, n):
            if rng.uniform() < 0.5:
                ec[(topo[i], topo[j])] = rng.uniform(
                    0, 10, size=(len(nc[topo[i]]), len(nc[topo[j]])))
    return global_search.SchemeProblem(nc, ec, topo)


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_dp_equals_brute_force(seed):
    prob = _random_problem(seed)
    assert abs(global_search.dp_search(prob).objective
               - global_search.brute_force(prob).objective) < 1e-9


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_pbqp_quality_bound(seed):
    """Paper §3.3.2: the approximation achieves >= 88% of the DP optimum.
    (Quality = opt/approx for a minimization objective.)"""
    prob = _random_problem(seed)
    approx = global_search.pbqp_search(prob)
    best = global_search.brute_force(prob)
    assert approx.objective >= best.objective - 1e-9
    assert best.objective / max(approx.objective, 1e-12) >= 0.88


def test_pbqp_exact_on_chain():
    """Chains reduce by RI only -> provably optimal, exact flag set."""
    rng = np.random.default_rng(3)
    topo = [f"n{i}" for i in range(6)]
    nc = {m: rng.uniform(0, 10, size=3) for m in topo}
    ec = {(topo[i], topo[i + 1]): rng.uniform(0, 10, size=(3, 3))
          for i in range(5)}
    prob = global_search.SchemeProblem(nc, ec, topo)
    sol = pbqp.solve_copy(global_search.to_pbqp(prob))
    assert sol.exact
    assert abs(sol.objective
               - global_search.brute_force(prob).objective) < 1e-9


def test_dp_intractable_falls_back():
    """A dense 12-node clique with 6 alternatives blows the DP budget;
    solve() must fall back to PBQP (the paper's 5-minute switch)."""
    rng = np.random.default_rng(0)
    topo = [f"n{i}" for i in range(12)]
    nc = {m: rng.uniform(0, 10, size=6) for m in topo}
    ec = {(topo[i], topo[j]): rng.uniform(0, 10, size=(6, 6))
          for i in range(12) for j in range(i + 1, 12)}
    prob = global_search.SchemeProblem(nc, ec, topo)
    with pytest.raises(global_search.Intractable):
        global_search.dp_search(prob, max_states=1000)
    sol = global_search.solve(prob, dp_state_budget=1000)
    assert sol.method.startswith("pbqp")


def test_zero_transform_edges_prefer_matching_layouts():
    """With equal node costs, the DP must pick matching (oc, ic) blocks."""
    nc = {"a": np.zeros(2), "b": np.zeros(2)}
    # scheme 0 = block 16, scheme 1 = block 32; mismatch costs 1.0
    m = np.array([[0.0, 1.0], [1.0, 0.0]])
    prob = global_search.SchemeProblem(nc, {("a", "b"): m}, ["a", "b"])
    sol = global_search.dp_search(prob)
    assert sol.objective == 0.0
    assert sol.assignment["a"] == sol.assignment["b"]
