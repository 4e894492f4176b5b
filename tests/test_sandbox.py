"""Safe code injection: install-time verifier + runtime sandbox contracts.

Hostile code is the threat model the paper's headline capability creates:
remotely injected ifuncs that recursively propagate themselves cannot be
extended on trust in a shared fabric.  Every scenario here must end the
same way — a loud SandboxViolation, a per-reason ``PEStats.refusals``
bump, and the offending digest quarantined cluster-wide (uninstalled,
sender caches forgotten, queued frames dropped, in-flight CQ futures
degraded) — with **zero effect on benign traffic** sharing the fabric.

The disabled path is equally load-bearing: with the default config no
verification runs at all (``verifier.verifies == 0`` everywhere), which
is what keeps the disabled runtime bit-for-bit the unsandboxed one.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    A_PUBLISH,
    A_RETURN,
    ACTION_WIDTH,
    Cluster,
    CompletionQueue,
    IFunc,
    PEStats,
    SandboxConfig,
    SandboxViolation,
    make_gossiper,
    make_tsi,
)
from repro.core.verify import count_ops

I32 = np.int32
TARGETS = ("cpu-host", "cpu-bf2")  # two triples keep toolchain builds cheap


# ------------------------------------------------------------- hostile code
@pytest.fixture(scope="module")
def tsi():
    return make_tsi()


@pytest.fixture(scope="module")
def gossiper():
    return make_gossiper()


@pytest.fixture(scope="module")
def rndv_thief():
    """Declares a transport rendezvous staging region as its linked dep —
    the one region class no shipped code may ever touch."""

    def entry(payload: jax.Array, region: jax.Array) -> jax.Array:
        return region + payload

    return IFunc.build(
        name="rndv_thief",
        fn=entry,
        payload_aval=jax.ShapeDtypeStruct((1,), I32),
        dep_avals=(jax.ShapeDtypeStruct((1,), I32),),
        deps=("region:rndv/client/0",),
        abi="update",
        targets=TARGETS,
    )


@pytest.fixture(scope="module")
def action_bomb():
    """Emits an A_RETURN row without declaring a ``returns:`` dep — an
    action its capability stamp can never contain."""

    def entry(payload: jax.Array) -> jax.Array:
        row = jnp.zeros(ACTION_WIDTH, I32)
        return row.at[0].set(A_RETURN).at[2].set(1).at[3].set(payload[0])

    return IFunc.build(
        name="action_bomb",
        fn=entry,
        payload_aval=jax.ShapeDtypeStruct((1,), I32),
        abi="xrdma",
        targets=TARGETS,
    )


@pytest.fixture(scope="module")
def reminter():
    """A rogue gossiper: structurally the ring gossiper, but each arrival
    re-publishes itself granting ttl **9** — re-minting a deeper publish
    budget than any sandbox ceiling in these tests admits."""

    def entry(
        payload: jax.Array, log: jax.Array, meta: jax.Array
    ) -> tuple[jax.Array, jax.Array]:
        me, n = meta[0], meta[1]
        nxt = jnp.where(me + 1 >= n, 0, me + 1)
        row = jnp.zeros(ACTION_WIDTH, I32)
        row = row.at[0].set(A_PUBLISH).at[1].set(nxt).at[2].set(3)
        row = row.at[3].set(9).at[5].set(payload[1])  # p0 = granted ttl 9
        return log + 1, row

    return IFunc.build(
        name="reminter",
        fn=entry,
        payload_aval=jax.ShapeDtypeStruct((2,), I32),
        dep_avals=(
            jax.ShapeDtypeStruct((2,), I32),
            jax.ShapeDtypeStruct((2,), I32),
        ),
        deps=("region:gossip_log", "cap:gossip_meta"),
        abi="propagate",
        targets=TARGETS,
    )


def counter_cluster(tsi, n_servers=2, sandbox=None):
    cl = Cluster(n_servers=n_servers)
    for pe in cl.servers:
        pe.register_region("counter", np.zeros(1, I32))
    cl.toolchain.publish(tsi)
    if sandbox is not None:
        cl.set_sandbox(sandbox)
    return cl


def counters(cl):
    return [int(pe.region("counter")[0]) for pe in cl.servers]


def gossip_cluster(ifunc, n_servers=2, sandbox=None):
    cl = Cluster(n_servers=n_servers)
    n = n_servers + 1
    for i, pe in enumerate(cl.pes()):
        pe.register_region("gossip_log", np.zeros(2, I32))
        pe.register_cap("gossip_meta", np.array([i, n], I32))
    cl.toolchain.publish(ifunc)
    if sandbox is not None:
        cl.set_sandbox(sandbox)
    return cl


# ========================================================== install verifier
class TestInstallVerifier:
    def test_rndv_region_always_refused(self, rndv_thief):
        """Transport staging regions are categorically out of bounds, even
        under the most permissive enabled config: refused at install,
        quarantined, never resolvable."""
        cl = Cluster(1)
        cl.toolchain.publish(rndv_thief)
        cl.set_sandbox(SandboxConfig.on())
        cl.client.send_ifunc("server0", "rndv_thief", np.array([1], I32))
        with pytest.raises(SandboxViolation, match="rndv"):
            cl.servers[0].poll()
        srv = cl.servers[0]
        assert srv.stats.refusals["verify_region"] == 1
        assert not srv.target_cache.has_name("rndv_thief")
        assert rndv_thief.digest.hex() in srv.verifier.quarantined

    def test_region_whitelist_enforced(self, tsi):
        """A non-empty ``allowed_regions`` is a hard whitelist: tsi's
        ``region:counter`` passes only when listed."""
        ok = counter_cluster(
            tsi, sandbox=SandboxConfig.on(allowed_regions=("counter",))
        )
        ok.client.send_ifunc("server0", "tsi", np.array([5], I32))
        ok.drain()
        assert counters(ok) == [5, 0]

        bad = counter_cluster(
            tsi, sandbox=SandboxConfig.on(allowed_regions=("other",))
        )
        bad.client.send_ifunc("server0", "tsi", np.array([5], I32))
        with pytest.raises(SandboxViolation, match="counter"):
            bad.servers[0].poll()
        assert bad.servers[0].stats.refusals["verify_region"] == 1
        assert counters(bad) == [0, 0]

    def test_op_budget_refused_before_compile(self, tsi):
        """A slice over the instruction budget is refused at install —
        before XLA compiles anything (the compile is itself a resource)."""
        cl = counter_cluster(tsi, sandbox=SandboxConfig.on(max_ops=1))
        srv = cl.servers[0]
        jit0 = srv.stats.jit_ms_total
        cl.client.send_ifunc("server0", "tsi", np.array([5], I32))
        with pytest.raises(SandboxViolation, match="ops"):
            srv.poll()
        assert srv.stats.refusals["verify_ops"] == 1
        assert srv.stats.jit_ms_total == jit0  # refusal cost no compile
        assert not srv.target_cache.has_name("tsi")

    def test_cold_verify_once_then_stamp_hits(self, tsi):
        """One cold verification per (PE, digest); every later resolve of
        the same digest — including warm digest-only frames — is a stamp
        dict hit.  This is the ~0 warm-publish overhead the benchmark pins."""
        cl = counter_cluster(tsi, sandbox=SandboxConfig.on())
        for _ in range(4):
            cl.client.send_ifunc("server0", "tsi", np.array([1], I32))
            cl.drain()
        ver = cl.servers[0].verifier
        assert ver.verifies == 1
        assert ver.stamp_hits >= 3
        assert counters(cl) == [4, 0]

    def test_warm_tree_publish_is_all_stamp_hits(self, tsi):
        """Second tree publish of an already-stamped digest verifies
        nothing anywhere: the whole warm tree rides the stamp cache."""
        cl = counter_cluster(tsi, n_servers=4, sandbox=SandboxConfig.on())
        cl.client.publish_ifunc("tsi", np.array([5], I32))
        cl.drain()
        cold = {pe.name: pe.verifier.verifies for pe in cl.servers}
        assert all(v == 1 for v in cold.values())
        cl.client.publish_ifunc("tsi", np.array([2], I32))
        cl.drain()
        assert all(pe.verifier.verifies == 1 for pe in cl.servers)
        assert all(pe.verifier.stamp_hits >= 1 for pe in cl.servers)
        assert counters(cl) == [7, 7, 7, 7]

    def test_disabled_path_runs_zero_verification(self, tsi):
        """Default config: no hook fires, no stamp is minted, no refusal
        is counted — the pre-sandbox runtime, bit-for-bit."""
        cl = counter_cluster(tsi, n_servers=4)  # sandbox left at default
        cl.client.publish_ifunc("tsi", np.array([5], I32))
        cl.drain()
        for pe in cl.pes():
            assert not pe.sandbox.enabled
            assert pe.verifier.verifies == 0
            assert pe.verifier.stamp_hits == 0
            assert pe.verifier.stamps == {}
        assert cl.refusals() == {}
        assert counters(cl) == [5, 5, 5, 5]

    def test_count_ops_is_deterministic(self, tsi):
        blob = tsi.fat.extract("cpu-bf2").blob
        exported = jax.export.deserialize(blob)
        assert count_ops(exported) == count_ops(exported) > 0
        assert count_ops(None) == 0


# ============================================================ runtime quotas
class TestRuntimeQuotas:
    def test_action_outside_stamp_refused(self, action_bomb):
        """A_RETURN without a ``returns:`` dep: the capability stamp never
        grants it, so the first emitted row is refused and the digest
        quarantined — before the runtime dereferences the missing dep."""
        cl = Cluster(1)
        cl.toolchain.publish(action_bomb)
        cl.set_sandbox(SandboxConfig.on())
        cl.client.send_ifunc("server0", "action_bomb", np.array([3], I32))
        with pytest.raises(SandboxViolation, match="A_RETURN"):
            cl.servers[0].poll()
        srv = cl.servers[0]
        assert srv.stats.refusals["verify_action"] == 1
        assert action_bomb.digest.hex() in srv.verifier.quarantined

    def test_invoke_budget_burn_stops_at_quota(self, tsi):
        """max_invokes=3: the fourth invoke is refused *before* dispatch —
        the counter proves exactly three executions happened."""
        cl = counter_cluster(tsi, sandbox=SandboxConfig.on(max_invokes=3))
        for _ in range(3):
            cl.client.send_ifunc("server0", "tsi", np.array([1], I32))
            cl.drain()
        assert counters(cl) == [3, 0]
        cl.client.send_ifunc("server0", "tsi", np.array([1], I32))
        with pytest.raises(SandboxViolation, match="quota"):
            cl.servers[0].poll()
        assert counters(cl) == [3, 0]  # refused invoke never ran
        assert cl.servers[0].stats.refusals["quota_invokes"] == 1

    def test_per_invoke_payload_cap(self, tsi):
        """A single payload over the per-invoke byte cap is refused on its
        first arrival (tsi's payload is 4 bytes; cap it at 2)."""
        cl = counter_cluster(
            tsi, sandbox=SandboxConfig.on(max_invoke_payload_bytes=2)
        )
        cl.client.send_ifunc("server0", "tsi", np.array([5], I32))
        with pytest.raises(SandboxViolation, match="payload"):
            cl.servers[0].poll()
        assert counters(cl) == [0, 0]
        assert cl.servers[0].stats.refusals["quota_payload"] == 1

    def test_cumulative_payload_quota(self, tsi):
        """4-byte payloads against a 10-byte cumulative quota: two invokes
        fit (8B), the third (12B) is refused."""
        cl = counter_cluster(
            tsi, sandbox=SandboxConfig.on(max_payload_bytes=10)
        )
        for _ in range(2):
            cl.client.send_ifunc("server0", "tsi", np.array([1], I32))
            cl.drain()
        assert counters(cl) == [2, 0]
        cl.client.send_ifunc("server0", "tsi", np.array([1], I32))
        with pytest.raises(SandboxViolation, match="cumulative"):
            cl.servers[0].poll()
        assert counters(cl) == [2, 0]
        assert cl.servers[0].stats.refusals["quota_payload"] == 1

    def test_publish_fanout_quota(self, gossiper):
        """The ring gossiper re-publishes once per arrival; with
        max_publish_fanout=1 its second arrival at the same PE blows the
        cumulative fan-out ledger."""
        cl = gossip_cluster(
            gossiper, sandbox=SandboxConfig.on(max_publish_fanout=1)
        )
        cl.client.send_ifunc("server0", "gossiper", np.array([1, 5], I32))
        cl.drain()  # hop lands on server1 and stops (hops exhausted)
        assert cl.servers[0].region("gossip_log").tolist() == [1, 5]
        cl.client.send_ifunc("server0", "gossiper", np.array([1, 7], I32))
        with pytest.raises(SandboxViolation, match="fan-out"):
            cl.servers[0].poll()
        assert cl.servers[0].stats.refusals["quota_fanout"] == 1


# =============================================================== ttl ceiling
class TestTtlCeiling:
    def test_remint_beyond_config_ceiling(self, reminter):
        """Directly-sent code is stamped with the config ceiling (4); its
        attempt to grant ttl 9 on re-publish is refused at the mint."""
        cl = gossip_cluster(
            reminter, sandbox=SandboxConfig.on(max_publish_ttl=4)
        )
        cl.client.send_ifunc("server0", "reminter", np.array([1, 5], I32))
        with pytest.raises(SandboxViolation, match="ttl 9"):
            cl.servers[0].poll()
        srv = cl.servers[0]
        assert srv.stats.refusals["verify_ttl"] == 1
        assert reminter.digest.hex() in srv.verifier.quarantined
        # the refused publish never travelled: server1 saw nothing
        assert cl.servers[1].region("gossip_log").tolist() == [0, 0]

    def test_remint_is_quarantined_cluster_wide(self, reminter, tsi):
        """Beside benign sends on the same fabric, the re-minter ends
        refused at the mint, banished from every PE, spread nowhere and
        refused on sight at re-entry; the benign counters stay exact."""
        cl = gossip_cluster(reminter)
        for pe in cl.servers:
            pe.register_region("counter", np.zeros(1, I32))
        cl.toolchain.publish(tsi)
        cl.set_sandbox(SandboxConfig.on(max_publish_ttl=4))

        def benign():
            for pe in cl.servers:
                cl.client.send_ifunc(pe.name, "tsi", np.array([5], I32))
            cl.drain()

        benign()
        cl.client.send_ifunc("server0", "reminter", np.array([1, 5], I32))
        with pytest.raises(SandboxViolation, match="ttl 9"):
            cl.servers[0].poll()
        cl.drain()
        hexd = reminter.digest.hex()
        for pe in cl.pes():
            assert hexd in pe.verifier.quarantined
            assert not pe.target_cache.has_name("reminter")
        assert cl.servers[1].region("gossip_log").tolist() == [0, 0]
        cl.client.send_ifunc("server1", "reminter", np.array([1, 5], I32))
        with pytest.raises(SandboxViolation, match="quarantined"):
            cl.servers[1].poll()
        cl.drain()
        benign()
        assert counters(cl) == [10, 10]
        assert cl.refusals()["verify_ttl"] == 1

    def test_remint_beyond_admitted_hop_ttl(self, reminter):
        """A PUBLISH-delivered slice is clamped to its *admitting hop's*
        remaining ttl even under a loose config: admitted at ttl 2, its
        grant of 9 is a re-mint and is refused."""
        cl = gossip_cluster(reminter, sandbox=SandboxConfig.on())
        assert cl.client.sandbox.max_publish_ttl >= 9  # config alone allows
        cl.client.publish_to(
            "server0", "reminter", np.array([1, 5], I32), ttl=2
        )
        with pytest.raises(SandboxViolation, match="ceiling 2"):
            cl.servers[0].poll()
        assert cl.servers[0].stats.refusals["verify_ttl"] == 1


# ================================================================ quarantine
class TestQuarantine:
    def test_quarantine_is_cluster_wide(self, tsi):
        """A quota refusal on one PE banishes the digest everywhere: every
        target cache uninstalls, every sender cache forgets, later frames
        for it are refused on sight — and benign state is untouched."""
        cl = counter_cluster(
            tsi, n_servers=4, sandbox=SandboxConfig.on(max_invokes=1)
        )
        cl.client.publish_ifunc("tsi", np.array([5], I32))
        cl.drain()
        assert counters(cl) == [5, 5, 5, 5]
        hexd = tsi.digest.hex()
        cl.client.send_ifunc("server0", "tsi", np.array([1], I32))
        with pytest.raises(SandboxViolation, match="quota"):
            cl.servers[0].poll()
        for pe in cl.pes():
            assert hexd in pe.verifier.quarantined
            assert not pe.target_cache.has_name("tsi")
            for peer in ("server0", "server1", "server2", "server3", "client"):
                assert not pe.sender_cache.has(peer, hexd)
        # hostile containment had zero effect on already-retired state
        assert counters(cl) == [5, 5, 5, 5]
        # a later frame for the banished digest is refused on sight
        cl.client.send_ifunc("server1", "tsi", np.array([1], I32))
        with pytest.raises(SandboxViolation, match="quarantined"):
            cl.servers[1].poll()
        roll = cl.refusals()
        assert roll["quota_invokes"] == 1
        assert roll["verify_quarantined"] >= 1
        assert counters(cl) == [5, 5, 5, 5]

    def test_quarantine_drops_queued_frames(self, tsi):
        """Frames already queued behind a credit window when their digest
        is banished are purged at the sender, counted per-PE — the fabric
        never carries banned code it already knows is banned."""
        cl = counter_cluster(tsi, sandbox=SandboxConfig.on(max_invokes=1))
        cl.set_flow(credit_window=1)
        cl.client.send_ifunc("server0", "tsi", np.array([1], I32))
        cl.drain()
        assert counters(cl) == [1, 0]
        # three more: one transmits into the window, two queue at the client
        for _ in range(3):
            cl.client.send_ifunc("server0", "tsi", np.array([1], I32))
        assert cl.client.wire.queued_credit_frames() == 2
        with pytest.raises(SandboxViolation, match="quota"):
            cl.servers[0].poll()
        assert cl.client.wire.queued_credit_frames() == 0
        assert cl.client.stats.refusals["quarantine_drop"] == 2
        cl.drain()
        assert counters(cl) == [1, 0]  # nothing banned ever ran

    def test_quarantine_degrades_inflight_cq_futures(self, tsi):
        """An in-flight completion-queue future whose code is banished
        reads as expired and degrades through the validity-mask path —
        the PR 6 contract — instead of hanging; its slot is recycled."""
        cl = counter_cluster(tsi, sandbox=SandboxConfig.on())
        cq = CompletionQueue(cl.client, shape=(1,), dtype=I32, max_slots=2)
        fut = cl.client.submit(
            "server0", "tsi", np.array([7], I32), cq, expected=1
        )
        assert fut is not None and not fut.expired()
        cl.client.verifier.quarantine(tsi.digest.hex(), "tsi")
        assert fut.poisoned and fut.expired()
        rows, mask = fut.result_partial()
        assert not mask.any()  # nothing arrived, loudly attributed
        assert cq.free_slots == 2  # slot recycled, no leak


# ==================================================== tenancy + config merge
class TestStrictestMerge:
    def test_empty_is_disabled_default(self):
        assert SandboxConfig.strictest([]) == SandboxConfig()

    def test_quotas_take_tightest_nonzero(self):
        merged = SandboxConfig.strictest(
            [
                SandboxConfig.on(max_invokes=10, max_payload_bytes=0),
                SandboxConfig.on(max_invokes=3, max_payload_bytes=64),
            ]
        )
        assert merged.enabled
        assert merged.max_invokes == 3
        assert merged.max_payload_bytes == 64  # 0 = unlimited never wins

    def test_actions_intersect_regions_union_iff_all_restrict(self):
        a = SandboxConfig.on(
            allowed_actions=(0, 4, 5), allowed_regions=("x",)
        )
        b = SandboxConfig.on(
            allowed_actions=(0, 1, 4), allowed_regions=("y",)
        )
        merged = SandboxConfig.strictest([a, b])
        assert merged.allowed_actions == (0, 4)
        assert merged.allowed_regions == ("x", "y")
        # one unrestricted class -> declared-region semantics stand
        loose = SandboxConfig.strictest([a, SandboxConfig.on()])
        assert loose.allowed_regions == ()

    def test_ttl_ceiling_is_min(self):
        merged = SandboxConfig.strictest(
            [SandboxConfig.on(max_publish_ttl=8), SandboxConfig.on()]
        )
        assert merged.max_publish_ttl == 8


class TestTenantThreading:
    def test_router_installs_strictest_policy_and_serves(self):
        """A TenantClass declaring a sandbox makes the router install the
        strictest merge cluster-wide — and the gather substrate verifies
        clean under it (oracle-identical results, zero refusals)."""
        from repro.runtime.embed_service import EmbedShardService
        from repro.runtime.tenancy import TenantClass, TenantRouter

        cl = Cluster(2)
        svc = EmbedShardService(cl, vocab=32, dim=4, n_keys=4, max_slots=8)
        router = TenantRouter(
            svc,
            [
                TenantClass("a", sandbox=SandboxConfig.on(max_invokes=500)),
                TenantClass("b", sandbox=SandboxConfig.on(max_invokes=200)),
                TenantClass("c"),  # no policy declared
            ],
        )
        assert cl.client.sandbox.enabled
        assert cl.client.sandbox.max_invokes == 200  # strictest won
        keys = np.array([3, 17, 30], I32)
        rid = router.submit("a", keys)
        assert rid is not None
        done = []
        while svc.queue or svc.active:
            done += router.tick()
        (req,) = done
        assert not req.degraded
        np.testing.assert_array_equal(req.rows, svc.table[keys])
        assert cl.refusals() == {}
        # the substrate's code really went through verification
        assert any(pe.verifier.verifies > 0 for pe in cl.pes())

    def test_no_declared_sandbox_leaves_cluster_unsandboxed(self):
        from repro.runtime.embed_service import EmbedShardService
        from repro.runtime.tenancy import TenantClass, TenantRouter

        cl = Cluster(2)
        svc = EmbedShardService(cl, vocab=32, dim=4, n_keys=4, max_slots=8)
        TenantRouter(svc, [TenantClass("a"), TenantClass("b")])
        assert not cl.client.sandbox.enabled


# ================================================================ back-compat
class TestRefusalAccounting:
    def test_legacy_properties_mirror_the_dict(self):
        stats = PEStats()
        stats.refuse("publish_ttl")
        stats.refuse("publish_cycle", 2)
        stats.refuse("publish_digest")
        assert stats.publish_refused_ttl == 1
        assert stats.publish_refused_cycle == 2
        assert stats.publish_refused_digest == 1
        assert stats.as_dict()["refusals"] == {
            "publish_ttl": 1,
            "publish_cycle": 2,
            "publish_digest": 1,
        }

    def test_cluster_rollup_sums_across_pes(self, tsi):
        cl = counter_cluster(tsi, n_servers=2)
        cl.servers[0].stats.refuse("quota_invokes")
        cl.servers[1].stats.refuse("quota_invokes", 2)
        cl.client.stats.refuse("verify_ttl")
        assert cl.refusals() == {"quota_invokes": 3, "verify_ttl": 1}
