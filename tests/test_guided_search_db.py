"""Deterministic search-machinery tests: guided local search with a
*stubbed* measured runner (scripted costs, zero wall-clock — search was
previously only covered via flaky timing), and schedule-database
round-trips in the ``BENCH_variants_db.json`` format including the new
fused_pool / concat-write workload flags and unknown-key forward compat.

Deliberately hypothesis-free so the module runs everywhere."""
import dataclasses
import json

import pytest

from repro.core import local_search as ls
from repro.core.local_search import (ScheduleDatabase, _wl_key,
                                     guided_local_search)
from repro.core.schedule import (VARIANTS, ConvWorkload,
                                 candidate_schedules)

WL = ConvWorkload(batch=1, in_channels=64, out_channels=64, height=28,
                  width=28, kh=3, kw=3, stride=1, pad=1)


# ---------------------------------------------------------------------------
# Deterministic guided search: stubbed measured_runner, no wall clock
# ---------------------------------------------------------------------------

def test_guided_search_deterministic_stub(monkeypatch):
    """Variant shortlisting + winner selection with *scripted* costs: every
    lowering variant must reach the measurement stage (per_variant slots),
    and the scripted cheapest (variant, blocking) must win — without a
    single wall-clock sample."""
    measured = []
    # script: patch_gemm strictly cheapest, per_tap strictly worst; within a
    # variant larger ic_bn is cheaper, so the winner is fully determined
    order = {"patch_gemm": 1.0, "tap_stack": 2.0, "scan": 3.0, "per_tap": 4.0}

    def scripted(wl, s, repeats=3):
        measured.append(s)
        return order[s.resolved_variant()] * 1e-3 + 1e-6 / s.ic_bn

    monkeypatch.setattr(ls, "measured_runner", scripted)
    res = guided_local_search(WL, top_k=4, per_variant=2)

    assert res.measured is True
    assert res.search_budget == (4, 2)
    # every variant of the workload's space (the four tap loop nests: a
    # 64-channel conv never enumerates xla_conv) was shortlisted and
    # measured at least per_variant times (dedup by (ic_bn, oc_bn, variant)
    # can only add distinct entries)
    space = {s.resolved_variant() for s in candidate_schedules(WL)}
    assert space == set(VARIANTS) - {"xla_conv"}
    by_variant = {v: [s for s in measured if s.resolved_variant() == v]
                  for v in space}
    for v in space:
        assert len(by_variant[v]) >= 2, f"variant {v} not shortlisted"
    # no duplicate measurements: the shortlist dedups identical computations
    keys = [(s.ic_bn, s.oc_bn, s.resolved_variant()) for s in measured]
    assert len(keys) == len(set(keys))
    # scripted winner: patch_gemm with the largest shortlisted ic_bn
    assert res.best.resolved_variant() == "patch_gemm"
    best_pg_ic = max(s.ic_bn for s in by_variant["patch_gemm"])
    assert res.best.ic_bn == best_pg_ic
    # the ranking is exactly the scripted costs, ascending
    costs = [r.cost_s for r in res.ranked]
    assert costs == sorted(costs)
    assert res.ranked[-1].schedule.resolved_variant() == "per_tap"


def test_search_measured_respects_budget(monkeypatch):
    """A shallow stubbed measured entry must not satisfy a deeper request."""
    calls = []

    def scripted(wl, s, repeats=3):
        calls.append(s)
        return 1e-3

    monkeypatch.setattr(ls, "measured_runner", scripted)
    db = ScheduleDatabase()
    db.search_measured(WL, top_k=2, per_variant=1)
    n_shallow = len(calls)
    db.search_measured(WL, top_k=2, per_variant=1)   # memoized
    assert len(calls) == n_shallow
    db.search_measured(WL, top_k=6, per_variant=2)   # deeper: re-searched
    assert len(calls) > n_shallow


# ---------------------------------------------------------------------------
# Schedule database: round-trip with the new fused flags + forward compat
# ---------------------------------------------------------------------------

FUSED_WL = ConvWorkload(batch=1, in_channels=3, out_channels=64, height=56,
                        width=56, kh=7, kw=7, stride=2, pad=3,
                        fused_bn=True, fused_relu=True,
                        fused_pool="max", pool_k=3, pool_stride=2,
                        pool_pad=1)
CONCAT_WL = ConvWorkload(batch=1, in_channels=64, out_channels=32, height=8,
                         width=8, kh=3, kw=3, pad=1,
                         concat_offset=64, concat_total=96)


def test_db_roundtrip_with_fused_pool_and_concat_flags(tmp_path):
    """Write -> load -> re-plan with BENCH_variants_db.json-format entries
    carrying the new fused_pool / concat flags."""
    path = tmp_path / "db.json"
    db = ScheduleDatabase(path)
    r_pool = db.search(FUSED_WL)
    r_cat = db.search(CONCAT_WL)
    assert _wl_key(FUSED_WL) != _wl_key(dataclasses.replace(
        FUSED_WL, fused_pool="", pool_k=0, pool_stride=0, pool_pad=0))
    assert "_cat64of96" in _wl_key(CONCAT_WL)

    db2 = ScheduleDatabase(path)                      # reload from disk
    r_pool2 = db2.search(FUSED_WL)                    # served from memo
    r_cat2 = db2.search(CONCAT_WL)
    assert r_pool2.workload == FUSED_WL               # flags survive
    assert r_cat2.workload == CONCAT_WL
    assert [x.schedule for x in r_pool2.ranked] == \
        [x.schedule for x in r_pool.ranked]
    assert [x.schedule for x in r_cat2.ranked] == \
        [x.schedule for x in r_cat.ranked]
    # the reloaded concat entries still respect the offset constraint
    for r in r_cat2.ranked:
        assert 64 % r.schedule.oc_bn == 0 and 96 % r.schedule.oc_bn == 0


def test_db_load_ignores_unknown_keys(tmp_path):
    """Forward compat: a database written by a newer version (extra workload
    and schedule keys) must load, dropping only the unknown fields."""
    path = tmp_path / "db.json"
    db = ScheduleDatabase(path)
    res = db.search(WL)
    blob = json.loads(path.read_text())
    for rec in blob.values():
        rec["workload"]["fused_int8_requant"] = True      # future flag
        rec["workload"]["pool_dilation"] = 2
        for r in rec["ranked"]:
            r["schedule"]["vector_width"] = 512            # future knob
        rec["search_protocol"] = "v99"                     # record-level
    path.write_text(json.dumps(blob))

    db2 = ScheduleDatabase(path)
    assert len(db2) == 1
    got = db2.search(WL)    # same key resolves: no re-search of known fields
    assert got.workload == WL
    assert [x.schedule for x in got.ranked] == \
        [x.schedule for x in res.ranked]
