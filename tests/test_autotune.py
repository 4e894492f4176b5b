"""Autotuner suite (PR 9): determinism, profile round trip, live loading.

Contracts pinned here:

* **Determinism** — same trace + same wire + same seed yields a
  bit-identical :class:`FlowProfile` (and identical search history), in
  memory and across a serialize/load cycle of the trace.
* **Profile round trip** — ``FlowProfile`` survives ``save``/``load``
  exactly; malformed profile files raise :class:`ProfileError`, never
  ``KeyError``/``JSONDecodeError``.
* **Live loading** — ``Cluster.set_flow(profile=<path>)`` installs every
  knob on every PE from the plain-JSON artifact, explicit kwargs win, and
  a tuned profile improves live ``modeled_us`` over the default runtime
  with oracle-identical results (the benchmark's claim, at test scale).
"""

import json

import numpy as np
import pytest

from repro.analysis import (
    KNOB_GRID,
    FlowProfile,
    ProfileError,
    ReplayModel,
    TraceError,
    autotune,
    capture,
    load_trace,
    replay_stats,
    save_trace,
)
from repro.analysis.autotune import RNDV_OFF
from repro.core import Cluster, PointerChaseApp, chase_ref

I32 = np.int32


@pytest.fixture(scope="module")
def captured():
    """One warm dapc run captured under the default runtime (the trace
    shape the tuner expects)."""
    cl = Cluster(n_servers=4, wire="thor_xeon")
    app = PointerChaseApp(cl, n_entries=512, max_slots=16, seed=0)
    rng = np.random.default_rng(1)
    starts = rng.integers(0, 512, 16).astype(I32)
    app.dapc(starts, 16)
    app.dapc(starts, 16, batching=True)
    with capture(cl) as rec:
        rep = app.dapc(starts, 16)
    want = np.array([chase_ref(app.table, s, 16) for s in starts], I32)
    np.testing.assert_array_equal(rep.results, want)
    return rec, rep.modeled_us


# ------------------------------------------------------------ determinism
def test_autotune_is_deterministic(captured):
    rec, _ = captured
    a = autotune(rec, seed=0)
    b = autotune(rec, seed=0)
    assert a.profile == b.profile
    assert a.as_dict() == b.as_dict()  # history, knob order, costs — all of it


def test_autotune_deterministic_across_serialization(captured, tmp_path):
    rec, _ = captured
    path = str(tmp_path / "run.jsonl")
    save_trace(rec, path)
    from_file = autotune(load_trace(path), seed=0)
    from_memory = autotune(rec, seed=0)
    assert from_file.as_dict() == from_memory.as_dict()


def test_seed_changes_knob_order_not_validity(captured):
    rec, _ = captured
    a = autotune(rec, seed=0)
    b = autotune(rec, seed=7)
    assert a.knob_order != b.knob_order  # the permutation really is seeded
    # both must still strictly beat the default on the replay estimate
    assert a.tuned_us < a.default_us
    assert b.tuned_us < b.default_us


def test_tuned_beats_default_on_replay(captured):
    rec, live_default_us = captured
    rep = autotune(rec, seed=0)
    model = ReplayModel(rec)
    # the default-profile estimate is exact: it re-prices the captured run
    assert model.cost(FlowProfile(wire="thor_xeon")) == pytest.approx(
        live_default_us, abs=1e-6
    )
    assert rep.default_us == pytest.approx(live_default_us, abs=1e-6)
    assert rep.tuned_us < rep.default_us
    assert rep.improvement_pct > 0
    assert rep.evaluations >= sum(len(v) for v in KNOB_GRID.values())


def test_autotune_unknown_wire_raises(captured):
    rec, _ = captured
    with pytest.raises(TraceError, match="unknown wire"):
        autotune(rec, wire="warp_drive")


# ------------------------------------------------------ profile round trip
def test_flowprofile_save_load_roundtrip(tmp_path):
    p = FlowProfile(
        wire="thor_bf2",
        batching=True,
        lanes=True,
        credit_window=16,
        poll_budget=8,
        eager_max=64,
        rndv_min=4096,
        zerocopy=True,
        k_code=3,
        tenant_budgets=(("bg", 4), ("hot", 32)),
    )
    path = str(tmp_path / "prof.json")
    p.save(path)
    assert FlowProfile.load(path) == p
    # and the dict form is plain JSON (what Cluster.set_flow consumes)
    assert json.load(open(path))["schema"] == "xrdma-flowprofile/1"


def test_flowprofile_defaults_are_runtime_defaults():
    p = FlowProfile(wire="ideal")
    assert not p.batching and not p.lanes and not p.zerocopy
    assert p.credit_window == 0 and p.poll_budget is None
    assert p.eager_max == 256 and p.rndv_min == RNDV_OFF
    assert p.k_code is None and p.tenant_budgets == ()


@pytest.mark.parametrize(
    "bad",
    [
        {"schema": "xrdma-flowprofile/999"},
        {"schema": "xrdma-flowprofile/1", "credit_window": "many"},
        {"schema": "xrdma-flowprofile/1", "rndv_min": [1]},
        {"schema": "xrdma-flowprofile/1", "tenant_budgets": {"t": "much"}},
        "not a dict",
        42,
    ],
)
def test_malformed_profile_raises_profile_error(bad):
    with pytest.raises(ProfileError):
        FlowProfile.from_dict(bad)


def test_profile_load_errors_are_typed(tmp_path):
    with pytest.raises(ProfileError, match="cannot read"):
        FlowProfile.load(str(tmp_path / "absent.json"))
    p = tmp_path / "garbage.json"
    p.write_text("{nope")
    with pytest.raises(ProfileError, match="invalid JSON"):
        FlowProfile.load(str(p))


# ------------------------------------------------------------ live loading
def test_set_flow_loads_profile_from_disk(tmp_path):
    prof = FlowProfile(
        wire="ideal",
        batching=True,
        lanes=True,
        credit_window=8,
        poll_budget=16,
        eager_max=64,
        rndv_min=4096,
        zerocopy=True,
        k_code=2,
        tenant_budgets=(("bg", 4),),
    )
    path = str(tmp_path / "tuned.json")
    prof.save(path)
    cl = Cluster(n_servers=2, wire="ideal")
    cl.set_flow(profile=path)
    for pe in cl.pes():
        assert pe.batching is True
        assert pe.lanes is True
        assert pe.credit_window == 8
        assert pe.poll_budget == 16
        assert pe.dataplane.eager_max == 64
        assert pe.dataplane.rndv_min == 4096
        assert pe.dataplane.zerocopy is True
        assert pe.propagation.topology == "kary" and pe.propagation.k == 2
        assert pe.wire.tenant_budgets == {"bg": 4}


def test_set_flow_explicit_kwargs_beat_profile():
    cl = Cluster(n_servers=2, wire="ideal")
    prof = FlowProfile(wire="ideal", lanes=True, credit_window=64, poll_budget=8)
    cl.set_flow(lanes=False, credit_window=4, profile=prof.as_dict())
    for pe in cl.pes():
        assert pe.lanes is False  # explicit kwarg won
        assert pe.credit_window == 4  # explicit kwarg won
        assert pe.poll_budget == 8  # profile filled the unset knob


def test_profile_apply_matches_set_flow(tmp_path):
    prof = FlowProfile(wire="ideal", batching=True, lanes=True, credit_window=8)
    a, b = Cluster(n_servers=2, wire="ideal"), Cluster(n_servers=2, wire="ideal")
    prof.apply(a)
    path = str(tmp_path / "p.json")
    prof.save(path)
    b.set_flow(profile=path)
    for pa, pb in zip(a.pes(), b.pes()):
        assert (pa.batching, pa.lanes, pa.credit_window) == (
            pb.batching, pb.lanes, pb.credit_window,
        )


def test_tuned_profile_improves_live_run_oracle_identical(captured, tmp_path):
    """The tuner's claim at test scale: tune from the captured trace,
    install through the disk loader, and the live tuned run beats the
    live default with bit-identical results."""
    rec, _ = captured
    tuned = autotune(rec, seed=0).profile
    cl = Cluster(n_servers=4, wire="thor_xeon")
    app = PointerChaseApp(cl, n_entries=512, max_slots=16, seed=0)
    rng = np.random.default_rng(1)
    starts = rng.integers(0, 512, 16).astype(I32)
    want = np.array([chase_ref(app.table, s, 16) for s in starts], I32)
    app.dapc(starts, 16)
    app.dapc(starts, 16, batching=True)
    default = app.dapc(starts, 16)
    np.testing.assert_array_equal(default.results, want)
    path = str(tmp_path / "tuned.json")
    tuned.save(path)
    cl.set_flow(profile=path)
    live = app.dapc(starts, 16, batching=tuned.batching, dataplane=tuned.dataplane())
    np.testing.assert_array_equal(live.results, want)
    assert live.modeled_us < default.modeled_us


def test_replay_fidelity_of_tuning_trace(captured):
    """The trace the tuner consumes reproduces the live counters — knob
    decisions are justified by the file alone."""
    rec, live_default_us = captured
    st, _ = replay_stats(rec)
    assert st.modeled_us == pytest.approx(live_default_us, abs=1e-9)
