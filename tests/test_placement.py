"""Heterogeneous placement layer: capability vectors, the cost-model
optimizer, the DPU predicate-pushdown filter, and restart hygiene."""

import numpy as np
import pytest

from repro.core import Capability, Cluster, TRIPLE_WIRE, WIRE_PROFILES
from repro.runtime.embed_service import EmbedShardService, FilterShardService
from repro.sharding.placement import PlacementOptimizer


def test_restart_readvertises_and_invalidates_plans():
    """A restarted PE must re-advertise its capability vector (fresh
    epoch) AND every cached placement plan priced against the dead
    incarnation must be dropped."""
    cl = Cluster(n_servers=2, hetero_wire=True)
    svc = FilterShardService(cl, vocab=256, dim=16, window=8)
    opt = PlacementOptimizer(cl)
    svc.plan_with(opt, [0])
    assert opt.cached_plans == 1
    epoch0 = cl.capabilities()["server0"].epoch
    cl.restart_server(0)
    cap = cl.capabilities()["server0"]
    assert cap is not None, "restarted PE did not re-advertise"
    assert cap.epoch > epoch0, "restart must mint a fresh capability epoch"
    assert opt.cached_plans == 0, (
        "cached plans routed to the restarted PE survived restart"
    )


# --------------------------------------------------------------- capabilities
def test_every_pe_advertises_at_connect():
    cl = Cluster(n_servers=3)
    caps = cl.capabilities()
    assert set(caps) == {"server0", "server1", "server2", "client"}
    srv, cli = caps["server0"], caps["client"]
    assert srv.isa == "cpu-bf2" and srv.wire == "thor_bf2"
    assert cli.isa == "cpu-host" and cli.wire == "thor_xeon"
    assert srv.mem_bw_class == "ddr-dpu" and cli.mem_bw_class == "ddr-host"
    # coefficients come straight from the calibrated wire profiles
    assert srv.alpha_us == WIRE_PROFILES["thor_bf2"].alpha_us
    assert cli.beta_Bus == WIRE_PROFILES["thor_xeon"].beta_Bus
    # epochs are distinct and monotone in connect order
    assert len({c.epoch for c in caps.values()}) == len(caps)


def test_kill_withdraws_capability():
    cl = Cluster(n_servers=2)
    cl.fabric.kill("server1")
    assert "server1" not in cl.capabilities()
    assert "server0" in cl.capabilities()


def test_hetero_pricing_uses_initiator_model():
    """With hetero accounting on, the same PUT costs different modeled
    time depending on who sends it; off, accounting is profile-uniform."""
    us = {}
    for hetero in (False, True):
        cl = Cluster(n_servers=1, wire="thor_bf2", hetero_wire=hetero)
        cl.servers[0].register_region("r", np.zeros(4096, np.uint8))
        cl.fabric.stats.reset()
        cl.fabric.get("client", "server0", "r", 0, 4096)
        us[hetero] = cl.fabric.stats.modeled_us
    xeon, bf2 = WIRE_PROFILES["thor_xeon"], WIRE_PROFILES["thor_bf2"]
    assert us[False] == pytest.approx(2 * bf2.alpha_us + 4096 / bf2.beta_Bus)
    # hetero: the client initiates, so its advertised thor_xeon model prices it
    assert us[True] == pytest.approx(2 * xeon.alpha_us + 4096 / xeon.beta_Bus)


# ------------------------------------------------------------- the cost model
def _mixed_optimizer(server_triple="cpu-bf2"):
    cl = Cluster(
        n_servers=2, wire="thor_xeon", server_triple=server_triple,
        hetero_wire=True,
    )
    return cl, PlacementOptimizer(cl)


PLAN_KW = dict(
    operand_bytes=24 * 96 * 4,
    result_bytes=24 * 96 * 4,
    request_payload_bytes=20,
    return_header_bytes=(3 + 24) * 4,
    op_name="filter",
    return_name="filter_return",
)


def test_optimizer_is_bit_deterministic():
    _, opt = _mixed_optimizer()
    a = opt.plan(requester="client", executor="server0", selectivity=0.25, **PLAN_KW)
    _, opt2 = _mixed_optimizer()
    b = opt2.plan(requester="client", executor="server0", selectivity=0.25, **PLAN_KW)
    assert a == b  # dataclass equality covers every priced float bit
    assert opt.priced == opt2.priced == 1
    # second identical call is a cache hit, not a re-price
    opt.plan(requester="client", executor="server0", selectivity=0.25, **PLAN_KW)
    assert opt.priced == 1


def test_selectivity_sweep_crosses_over():
    """Low selectivity pushes down; high selectivity pulls — on the same
    DPU-served cluster, purely from the survivor-byte term."""
    _, opt = _mixed_optimizer("cpu-bf2")
    lo = opt.plan(requester="client", executor="server0", selectivity=0.05, **PLAN_KW)
    hi = opt.plan(requester="client", executor="server0", selectivity=0.75, **PLAN_KW)
    assert lo.choice == "pushdown" and hi.choice == "pull"
    assert lo.pull_us == hi.pull_us  # pull side never depends on selectivity


def test_executor_overhead_flips_the_decision():
    """The hardware lever: the identical request refuses pushdown on the
    DPU (fat per-message o_us) but pushes down on the Xeon."""
    _, dpu = _mixed_optimizer("cpu-bf2")
    _, xeon = _mixed_optimizer("cpu-host")
    on_dpu = dpu.plan(requester="client", executor="server0", selectivity=0.75, **PLAN_KW)
    on_xeon = xeon.plan(requester="client", executor="server0", selectivity=0.75, **PLAN_KW)
    assert on_dpu.choice == "pull" and on_xeon.choice == "pushdown"


def test_unadvertised_peer_prices_with_fabric_profile():
    cl, opt = _mixed_optimizer()
    cl.fabric.kill("server0")
    d = opt.plan(requester="client", executor="server0", selectivity=0.5, **PLAN_KW)
    assert d.executor_epoch == 0  # the fallback capability, not a stale ad


# ------------------------------------------------------- the filter operator
@pytest.fixture(scope="module")
def filter_svc():
    cl = Cluster(n_servers=2, hetero_wire=True)
    return FilterShardService(cl, vocab=256, dim=16, window=8, seed=7)


def test_filter_matches_oracle_both_placements(filter_svc):
    svc = filter_svc
    los = svc.windows(6, seed=2)
    for sel in (0.05, 0.5, 0.95):
        th = svc.thresh_for_selectivity(sel)
        want = svc.oracle_filter(los, th)
        for arm in ("pushdown", "pull"):
            rep = svc.filter(los, th, placement=arm)
            for got, w in zip(rep.results, want):
                np.testing.assert_array_equal(got, w)


@pytest.fixture(scope="module")
def ab_cell():
    """A warm hetero-priced filter service homed on a server triple, with
    its optimizer and windows (built once per triple)."""
    cells = {}

    def get(triple):
        if triple not in cells:
            cl = Cluster(
                n_servers=2, wire="thor_xeon", server_triple=triple,
                hetero_wire=True,
            )
            svc = FilterShardService(cl, vocab=512, dim=96, window=24, seed=0)
            los = svc.windows(8, seed=1)
            svc.filter(los, 0.0, placement="pushdown")  # code moved, compiled
            cells[triple] = (svc, PlacementOptimizer(cl), los)
        return cells[triple]

    return get


@pytest.mark.parametrize("selectivity", [0.05, 0.25, 0.75])
@pytest.mark.parametrize("triple", ["cpu-bf2", "cpu-host"])
def test_optimizer_agrees_with_exhaustive_ab(ab_cell, triple, selectivity):
    """Run both placements, each oracle-identical, and score each with the
    fabric's hetero-priced wire time plus the per-message overheads and
    scan time it does not meter: the optimizer picks the cheaper one (the
    DPU pulls at 0.75, where the Xeon still pushes down)."""
    svc, opt, los = ab_cell(triple)
    caps = svc.cluster.capabilities()
    client, server = caps["client"], caps["server0"]
    n, operand = len(los), svc.n_keys * svc.dim * 4
    thresh = svc.thresh_for_selectivity(selectivity)
    want = svc.oracle_filter(los, thresh)
    scored = {}
    for arm in ("pushdown", "pull"):
        rep = svc.filter(los, thresh, placement=arm)
        for got, w in zip(rep.results, want):
            np.testing.assert_array_equal(got, w)
        if arm == "pushdown":
            assert rep.puts == 2 * n  # one request and one RETURN frame each
            extra = n * (client.o_us + server.o_us) + n * operand / server.scan_Bus
        else:
            extra = n * operand / client.scan_Bus
        scored[arm] = rep.modeled_us + extra
    winner = min(scored, key=scored.get)
    assert svc.plan_with(opt, los).choice == winner


def test_filter_wire_bytes_scale_with_selectivity(filter_svc):
    svc = filter_svc
    los = svc.windows(8, seed=3)
    th_lo = svc.thresh_for_selectivity(0.05)
    th_hi = svc.thresh_for_selectivity(0.95)
    svc.filter(los, th_lo)  # warm
    lo = svc.filter(los, th_lo).put_bytes
    hi = svc.filter(los, th_hi).put_bytes
    assert lo < hi, "ragged RETURNs must shrink with survivors"


def test_filter_rejects_misaligned_windows(filter_svc):
    svc = filter_svc
    boundary = svc.rows_per_shard - svc.n_keys // 2
    with pytest.raises(ValueError, match="crosses a shard boundary"):
        svc.filter([boundary], 0.0)
    with pytest.raises(ValueError, match="outside the table"):
        svc.filter([svc.vocab - 1], 0.0)


def test_placement_policy_threads_through_cluster():
    cl = Cluster(n_servers=2, hetero_wire=True)
    svc = FilterShardService(cl, vocab=256, dim=16, window=8)
    los = svc.windows(3, seed=1)
    th = svc.thresh_for_selectivity(0.5)
    cl.set_placement("pull")
    rep = svc.filter(los, th)
    assert rep.gets == 3 and rep.puts == 0
    cl.set_placement("pushdown")
    rep = svc.filter(los, th)
    assert rep.gets == 0 and rep.puts > 0
    cl.set_placement("auto")  # small operand: the model picks pull here
    rep = svc.filter(los, th)
    assert rep.gets == 3 and rep.puts == 0
    with pytest.raises(ValueError):
        cl.set_placement("sideways")


def test_flow_profile_carries_placement_knob():
    from repro.analysis.autotune import FlowProfile, KNOB_GRID

    assert "placement" in KNOB_GRID
    prof = FlowProfile(wire="thor_xeon", placement="pull")
    assert FlowProfile.from_dict(prof.as_dict()) == prof
    cl = Cluster(n_servers=1)
    prof.apply(cl)
    assert cl.placement_policy == "pull"


def test_gather_placement_param():
    cl = Cluster(n_servers=2)
    svc = EmbedShardService(cl, vocab=64, dim=8, n_keys=4)
    batches = [np.array([1, 40], np.int32), np.array([9], np.int32)]
    want = svc.oracle(batches)
    for placement in ("pushdown", "pull"):
        rep = svc.gather(batches, placement=placement)
        for got, w in zip(rep.results, want):
            np.testing.assert_array_equal(got, w)


def test_dapc_placement_pricing():
    """plan_chase prices DAPC vs per-hop GETs through the same model: a
    deep chase amortizes one request over many hops and pushes down."""
    _, opt = _mixed_optimizer()
    deep = opt.plan_chase(requester="client", executor="server0", depth=64)
    assert deep.choice == "pushdown"
    assert deep.pull_us > deep.pushdown_us


def test_capability_for_triple_table():
    for triple, wire in TRIPLE_WIRE.items():
        cap = Capability.for_triple(triple, "cpu" if "cpu" in triple else "tpu")
        assert cap.wire == wire
        assert cap.alpha_us == WIRE_PROFILES[wire].alpha_us
        assert cap.scan_Bus > 0
        assert cap.as_dict()["isa"] == triple
