"""N-department tenancy framework: policies, conservation, seed regression.

Covers the multi-layer refactor of the consolidation core:
  * the degenerate 2-tenant configuration reproduces the seed ST/WS
    simulator numbers EXACTLY (golden values recorded from the seed code
    before the refactor, including the RNG-sensitive fault-injection path);
  * property-based conservation invariant (sum of per-tenant alloc + free
    == total) over random N-tenant event sequences;
  * a >= 4-department mix (2 HPC + 2 WS + 1 best-effort) runs end-to-end
    with per-department benefit metrics under every cooperative policy;
  * node_failed reattribution can never desync total from the pool sum;
  * stride-based util_timeline downsampling keeps early history.
"""
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:       # container without hypothesis: property tests skip
    HAS_HYPOTHESIS = False

from repro.core.experiment import run_dynamic
from repro.core.policies import (AuctionEngine, DemandCappedIdlePolicy,
                                 PaperPolicy, POLICIES,
                                 ProportionalSharePolicy, SLOHeadroomEngine,
                                 Tenant, get_policy)
from repro.core.provision import TenantProvisionService
from repro.core.simulator import (ConsolidationSim, downsample_timeline)
from repro.core.traces import synthetic_sdsc_blue, worldcup_demand_events
from repro.core.types import (SimConfig, TenantSignals, TenantSpec,
                              paper_tenants)

DAY = 86400.0


# ------------------------------------------------------------- regression

# golden numbers recorded from the seed simulator (PR 1 tree) before the
# N-tenant refactor: the degenerate 2-tenant paper configuration must
# reproduce them bit-for-bit
GOLDEN = {
    ("kill", 160): dict(
        completed=268, killed=14, preemptions=0,
        avg_turnaround=8515.726519760798,
        median_turnaround=3870.290620908512,
        ws_unmet_node_seconds=0.0, ws_reclaim_events=279,
        st_node_seconds_used=16557597.830821756,
        st_avg_alloc=120.1109953703703, ws_avg_alloc=39.88900462962963),
    ("kill", 200): dict(
        completed=271, killed=16, preemptions=0,
        avg_turnaround=6460.359904890289,
        median_turnaround=2962.7737324380214,
        ws_unmet_node_seconds=0.0, ws_reclaim_events=279,
        st_node_seconds_used=21818117.363095924,
        st_avg_alloc=160.11099537037015, ws_avg_alloc=39.88900462962965),
}


@pytest.fixture(scope="module")
def seed_world():
    jobs = synthetic_sdsc_blue(seed=1, n_jobs=300, horizon=2 * DAY)
    ws = worldcup_demand_events(seed=1, horizon=2 * DAY)
    return jobs, ws


@pytest.mark.parametrize("size", [160, 200])
def test_degenerate_two_tenant_reproduces_seed_exactly(seed_world, size):
    jobs, ws = seed_world
    r = run_dynamic(jobs, ws, size, horizon=2 * DAY)
    for key, want in GOLDEN[("kill", size)].items():
        assert getattr(r, key) == want, (key, getattr(r, key), want)
    # the refactored result also carries per-department accounting
    assert set(r.tenants) == {"st", "ws"}
    assert r.tenants["st"].completed == r.completed
    assert r.tenants["ws"].unmet_node_seconds == r.ws_unmet_node_seconds
    assert r.policy == "paper"


def test_degenerate_checkpoint_and_faults_reproduce_seed(seed_world):
    jobs, ws = seed_world
    ck = run_dynamic(jobs, ws, 160, horizon=2 * DAY,
                     cfg=SimConfig(preempt_mode="checkpoint"))
    assert (ck.completed, ck.killed, ck.preemptions) == (281, 0, 26)
    assert ck.avg_turnaround == 9335.879255144253
    # fault injection exercises the RNG stream: identical numbers prove the
    # generalized _node_fail consumes randomness exactly like the seed
    fl = run_dynamic(jobs, ws, 160, horizon=2 * DAY,
                     cfg=SimConfig(node_mtbf=50 * DAY,
                                   node_repair_time=3600.0))
    assert (fl.completed, fl.killed) == (259, 15)
    assert fl.avg_turnaround == 9673.410274220416
    assert fl.st_avg_alloc == 120.00682870370359
    assert fl.ws_avg_alloc == 39.889004629629675


# --------------------------------------------------- 4-department end-to-end

def _mix_specs(horizon=DAY / 2, seed=0):
    return [
        TenantSpec("ws-a", "latency", priority=0,
                   demand=worldcup_demand_events(seed=seed, horizon=horizon)),
        TenantSpec("ws-b", "latency", priority=1,
                   demand=worldcup_demand_events(seed=seed + 7,
                                                 horizon=horizon)),
        TenantSpec("hpc-a", "batch", priority=2, weight=2.0,
                   jobs=synthetic_sdsc_blue(seed=seed, n_jobs=60,
                                            horizon=horizon, max_nodes=32)),
        TenantSpec("hpc-b", "batch", priority=3, weight=1.0,
                   jobs=synthetic_sdsc_blue(seed=seed + 1, n_jobs=60,
                                            horizon=horizon, max_nodes=32)),
        TenantSpec("be", "batch", priority=9, weight=0.5,
                   jobs=synthetic_sdsc_blue(seed=seed + 2, n_jobs=20,
                                            horizon=horizon, max_nodes=8)),
    ]


@pytest.mark.parametrize("policy", sorted(POLICIES))
def test_five_department_mix_end_to_end(policy):
    horizon = DAY / 2
    sim = ConsolidationSim(SimConfig(total_nodes=208), horizon=horizon,
                           tenants=_mix_specs(horizon), policy=policy)
    res = sim.run()
    assert set(res.tenants) == {"ws-a", "ws-b", "hpc-a", "hpc-b", "be"}
    assert res.policy == policy
    # per-department benefit metrics exist for every department
    bens = res.benefits()
    assert all(bens[n] for n in res.tenants)
    # conservation at every timeline row: allocs + free == total
    for row in sim.timeline:
        assert sum(row[1:]) == 208, row
    # every job accounted across departments
    assert res.submitted == 140
    # latency departments outrank batch: with 208 nodes their demand is met
    assert res.ws_unmet_node_seconds == 0.0
    # aggregates equal the per-department sums
    assert res.completed == sum(t.completed for t in res.tenants.values())


def test_demand_aware_policies_avoid_starving_lower_batch_departments():
    """Under the paper's greedy rule every idle node is dumped on the top
    batch department; demand-capped/proportional sharing let the others
    make progress too."""
    horizon = DAY / 2
    out = {}
    for policy in ("paper", "demand_capped", "proportional_share"):
        sim = ConsolidationSim(SimConfig(total_nodes=208), horizon=horizon,
                               tenants=_mix_specs(horizon), policy=policy)
        out[policy] = sim.run()
    assert out["paper"].tenants["hpc-b"].avg_alloc == 0.0
    for policy in ("demand_capped", "proportional_share"):
        assert out[policy].tenants["hpc-b"].completed > 0, policy
        assert out[policy].tenants["be"].completed > 0, policy


# ----------------------------------------------------------- policy units

def _tenants(*rows):
    ts = [Tenant(name, kind, priority=p, weight=w, demand=d, alloc=a)
          for name, kind, p, w, d, a in rows]
    return ts


def test_paper_policy_idle_is_single_grant_to_top_priority():
    pol = PaperPolicy()
    batch = _tenants(("a", "batch", 1, 1.0, 0, 0),
                     ("b", "batch", 2, 1.0, 0, 0))
    grants = pol.idle_grants(100, batch)
    assert grants == [(batch[0], 100)]


def test_demand_capped_policy_leaves_leftover_free():
    pol = DemandCappedIdlePolicy()
    batch = _tenants(("a", "batch", 1, 1.0, 30, 0),
                     ("b", "batch", 2, 1.0, 50, 0))
    grants = dict((t.name, n) for t, n in pol.idle_grants(100, batch))
    assert grants == {"a": 30, "b": 50}          # 20 stay free


def test_proportional_share_splits_by_weight():
    pol = ProportionalSharePolicy()
    batch = _tenants(("a", "batch", 1, 3.0, 1000, 0),
                     ("b", "batch", 2, 1.0, 1000, 0))
    grants = dict((t.name, n) for t, n in pol.idle_grants(100, batch))
    assert grants["a"] + grants["b"] == 100
    assert grants["a"] == 75 and grants["b"] == 25
    # saturation: a tenant whose demand is met frees its share
    batch = _tenants(("a", "batch", 1, 3.0, 10, 0),
                     ("b", "batch", 2, 1.0, 1000, 0))
    grants = dict((t.name, n) for t, n in pol.idle_grants(100, batch))
    assert grants == {"a": 10, "b": 90}


def test_get_policy_resolves_names_classes_instances():
    assert get_policy("paper").name == "paper"
    assert get_policy(PaperPolicy).name == "paper"
    assert get_policy(DemandCappedIdlePolicy()).name == "demand_capped"
    assert get_policy("slo_headroom").name == "slo_headroom"
    assert get_policy("auction").name == "auction"
    with pytest.raises(ValueError):
        get_policy("nope")


# --------------------------------------------------- two-phase engine units

def _wire_signals(t: Tenant, **kw):
    """Attach a fixed TenantSignals snapshot to a tenant record."""
    base = dict(name=t.name, kind=t.kind, alloc=t.alloc, demand=t.demand,
                weight=t.weight)
    base.update(kw)
    t.signals = lambda: TenantSignals(**base)
    return t


def test_slo_headroom_plan_orders_surplus_cheapest_then_drain():
    """Band order: latency surplus (most headroom first), batch by cheapest
    preemption, then latency drained down to the floor — never below it."""
    eng = SLOHeadroomEngine()
    claimant = Tenant("ws-hot", "latency", priority=0)
    ws_a = _wire_signals(Tenant("ws-a", "latency", priority=1, alloc=10,
                                floor=2),
                         demand=6, latency_headroom_s=20.0)
    ws_b = _wire_signals(Tenant("ws-b", "latency", priority=2, alloc=8,
                                floor=1),
                         demand=8, latency_headroom_s=5.0)
    hpc_cheap = _wire_signals(Tenant("hpc-cheap", "batch", priority=3,
                                     alloc=12),
                              demand=12, preemption_cost_s=30.0)
    hpc_dear = _wire_signals(Tenant("hpc-dear", "batch", priority=4,
                                    alloc=12),
                             demand=12, preemption_cost_s=900.0)
    tenants = [claimant, ws_a, ws_b, hpc_cheap, hpc_dear]
    plan = eng.plan_reclaim(100, tenants, claimant)
    order = [(s.victim, s.take) for s in plan]
    # band 1: only ws-a has surplus (10 alloc vs 6 demand)
    assert order[0] == ("ws-a", 4)
    # band 2: batch, cheapest preemption first
    assert order[1] == ("hpc-cheap", 12)
    assert order[2] == ("hpc-dear", 12)
    # band 3: latency drained most-headroom-first, down to floors only
    assert order[3] == ("ws-a", 4)       # 10 - floor 2 - surplus 4
    assert order[4] == ("ws-b", 7)       # 8 - floor 1
    # floors are never crossed by any step combination
    assert sum(n for v, n in order if v == "ws-a") == 10 - 2
    assert sum(n for v, n in order if v == "ws-b") == 8 - 1


def test_auction_reclaim_order_is_ascending_bid_batch_first():
    eng = AuctionEngine()
    claimant = Tenant("ws-hot", "latency", priority=0)
    # bids = weight x unmet demand
    hpc_busy = Tenant("hpc-busy", "batch", priority=3, alloc=10, demand=50,
                      weight=1.0)                       # bid 40
    hpc_idle = Tenant("hpc-idle", "batch", priority=2, alloc=10, demand=10,
                      weight=1.0)                       # bid 0
    ws_lo = Tenant("ws-lo", "latency", priority=1, alloc=6, demand=6,
                   weight=1.0)                          # bid 0
    tenants = [claimant, hpc_busy, hpc_idle, ws_lo]
    plan = eng.plan_reclaim(15, tenants, claimant)
    assert [s.victim for s in plan] == ["hpc-idle", "hpc-busy", "ws-lo"]
    # deficit 15 > hpc-idle's 10: the plan digs into hpc-busy, whose bid
    # (40) is the marginal price recorded for this claim
    assert eng.reclaim_price_n == 1
    assert eng.reclaim_price_sum == pytest.approx(40.0)
    snap = eng.state_snapshot()
    assert snap["engine"] == "auction"
    assert snap["last_plan"] == ["hpc-idle", "hpc-busy", "ws-lo"]


def test_auction_idle_grants_by_descending_bid_record_clearing_price():
    eng = AuctionEngine()
    a = Tenant("a", "batch", priority=1, alloc=0, demand=30, weight=1.0)
    b = Tenant("b", "batch", priority=2, alloc=0, demand=30, weight=3.0)
    grants = dict((t.name, n) for t, n in eng.idle_grants(40, [a, b]))
    # b bids 90, a bids 30: b is served first, a gets the remainder
    assert grants == {"b": 30, "a": 10}
    snap = eng.state_snapshot()
    assert snap["intervals"] == 1
    assert snap["clearing_price_mean"] == pytest.approx(30.0)  # lowest win
    assert snap["clearing_price_samples"] == [pytest.approx(30.0)]


def test_bid_weight_overrides_weight_in_bids():
    eng = AuctionEngine()
    a = Tenant("a", "batch", priority=1, alloc=0, demand=10, weight=1.0,
               bid_weight=9.0)
    b = Tenant("b", "batch", priority=2, alloc=0, demand=10, weight=5.0)
    grants = dict((t.name, n) for t, n in eng.idle_grants(10, [a, b]))
    assert grants == {"a": 10}           # a's bid 90 beats b's 50


@pytest.mark.parametrize("policy", sorted(POLICIES))
def test_claim_never_reclaims_below_latency_floor(policy):
    """Any engine's plan respects a latency victim's floor (the paper's
    behaviour is the floor=0 degenerate case)."""
    svc = TenantProvisionService(20, policy=policy)
    svc.register(Tenant("hot", "latency", priority=0))
    svc.register(Tenant("cold", "latency", priority=5, floor=3))
    svc.register(Tenant("hpc", "batch", priority=2,
                        on_force_release=lambda n: n))
    # fill: cold holds 8, hpc holds 12, nothing free
    got = svc.claim("cold", 8)
    assert got == 8
    svc.set_demand("hpc", 12)
    # hot claims everything: hpc fully drained, cold only down to floor 3
    got = svc.claim("hot", 20)
    assert svc.tenants["cold"].alloc >= 3
    assert got == 20 - 3
    svc.check()


def test_engine_reclaim_state_reaches_sim_results():
    horizon = DAY / 2
    sim = ConsolidationSim(SimConfig(total_nodes=96), horizon=horizon,
                           tenants=_mix_specs(horizon),
                           policy="slo_headroom")
    res = sim.run()
    ps = res.policy_state
    assert ps["engine"] == "slo_headroom"
    assert ps["reclaim_plans"] > 0
    # nodes drained per victim are attributed on the TenantResults too
    drained = {n: t.reclaimed_nodes for n, t in res.tenants.items()
               if t.reclaimed_nodes}
    assert drained and drained == {k: v for k, v in
                                   ps["victim_nodes"].items() if v}


def test_auction_clearing_prices_reach_sim_results():
    horizon = DAY / 2
    sim = ConsolidationSim(SimConfig(total_nodes=96), horizon=horizon,
                           tenants=_mix_specs(horizon), policy="auction")
    res = sim.run()
    ps = res.policy_state
    assert ps["engine"] == "auction"
    assert ps["intervals"] > 0
    assert ps["clearing_price_mean"] > 0.0
    assert ps["clearing_price_max"] >= ps["clearing_price_mean"]
    assert any(t.last_bid > 0 for t in res.tenants.values())


# ------------------------------------------- faults mid-reclaim (any engine)

@pytest.mark.parametrize("policy", sorted(POLICIES))
def test_node_failed_mid_reclaim_conserves_and_respects_floors(policy):
    """A node failure firing from INSIDE a victim's force-release hook (the
    runtime analogue: a host dies while the trainer checkpoints out) must
    not desync conservation, and the latency floor still holds."""
    svc = TenantProvisionService(24, policy=policy)
    svc.register(Tenant("hot", "latency", priority=0))
    svc.register(Tenant("cold", "latency", priority=5, floor=2))
    fired = {"n": 0}

    def flaky_release(n):
        # first reclaim round: a node dies mid-eviction, then release
        if fired["n"] == 0:
            fired["n"] = 1
            svc.node_failed("hpc")
        rec = svc.tenants["hpc"]
        return min(n, rec.alloc)

    svc.register(Tenant("hpc", "batch", priority=2,
                        on_force_release=flaky_release))
    assert svc.claim("cold", 6) == 6
    svc.set_demand("hpc", 18)
    got = svc.claim("hot", 24)           # forces hpc + cold reclaim
    assert fired["n"] == 1
    # node_failed fired inside the claim: total shrank by exactly 1
    assert svc.total == 23
    assert svc.tenants["cold"].alloc >= 2
    # conservation after the dust settles
    svc.check()
    assert got <= 24


@pytest.mark.parametrize("policy", sorted(POLICIES))
def test_mid_reclaim_failure_on_latency_victim_still_respects_floor(policy):
    """The floor cap is re-derived when a plan step is APPLIED: a node
    failure attributed to a latency victim mid-plan shrinks its alloc, and
    the stale plan-time cap must not drain it below its floor."""
    svc = TenantProvisionService(20, policy=policy)
    svc.register(Tenant("hot", "latency", priority=0))
    cold = svc.register(Tenant("cold", "latency", priority=5, floor=4))
    # cold's CMS reports its allocation fully used (no band-1 surplus for
    # slo_headroom), so every engine reclaims batch before touching it
    cold.signals = lambda: TenantSignals(
        name="cold", kind="latency", alloc=cold.alloc, demand=cold.alloc)

    def fail_on_cold_then_release(n):
        rec = svc.tenants["hpc"]
        if svc.tenants["cold"].alloc > 0:
            svc.node_failed("cold")      # dead node lands on the latency dept
        return min(n, rec.alloc)

    svc.register(Tenant("hpc", "batch", priority=2,
                        on_force_release=fail_on_cold_then_release))
    assert svc.claim("cold", 10) == 10
    svc.set_demand("hpc", 10)
    svc.claim("hot", 20)
    # cold lost 1 node to the failure (alloc 10 -> 9), then reclaim may
    # only take it down to its floor, not to plan-time (10 - 4 = 6) below it
    assert svc.tenants["cold"].alloc >= 4
    svc.check()


def test_auction_uncoverable_deficit_clears_at_zero():
    """Docstring contract: when the whole chain cannot cover the deficit
    the claim clears at price 0 (no marginal winning bid exists)."""
    eng = AuctionEngine()
    claimant = Tenant("hot", "latency", priority=0)
    hpc = Tenant("hpc", "batch", priority=2, alloc=5, demand=50, weight=1.0)
    eng.plan_reclaim(100, [claimant, hpc], claimant)
    assert eng.reclaim_price_n == 1
    assert eng.reclaim_price_sum == 0.0


def test_claim_credits_over_release_without_desync():
    """A victim that releases MORE than asked (e.g. a trainer shrinking by
    whole DP groups) must have the full release credited; the surplus flows
    back through the idle policy instead of desyncing counts."""
    svc = TenantProvisionService(16, policy="paper")
    released = []

    def dp_group_release(n):        # always sheds whole groups of 4
        take = -(-n // 4) * 4
        released.append(take)
        return take

    svc.register(Tenant("hpc", "batch", priority=1,
                        on_force_release=dp_group_release))
    svc.register(Tenant("ws", "latency", priority=0))
    svc.provision_idle()            # all 16 -> hpc
    got = svc.claim("ws", 2)        # forces a 4-device group release
    assert got == 2
    assert released == [4]
    # surplus 2 reflowed to hpc via the idle policy: 16 - 2 claimed
    assert svc.tenants["ws"].alloc == 2
    assert svc.tenants["hpc"].alloc == 14
    assert svc.free == 0
    svc.check()


# ------------------------------------------------- node_failed reattribution

def test_node_failed_empty_pool_reattributes_not_desyncs():
    svc = TenantProvisionService(10, policy="demand_capped")
    svc.register(Tenant("a", "batch", priority=1))
    svc.register(Tenant("b", "latency", priority=0))
    svc.set_demand("a", 10)                     # all 10 -> a
    assert svc.tenants["a"].alloc == 10 and svc.free == 0
    # failure attributed to the EMPTY free pool: reattributed (registration
    # order), never a silent total decrement
    svc.node_failed("free")
    assert svc.total == 9
    assert svc.tenants["a"].alloc == 9
    svc.check()
    # same for an empty tenant pool
    svc.node_failed("b")
    assert svc.total == 8 and svc.tenants["a"].alloc == 8
    svc.check()
    with pytest.raises(KeyError):
        svc.node_failed("zz")
    # empty cluster: impossible event raises instead of desyncing
    empty = TenantProvisionService(0)
    with pytest.raises(ValueError):
        empty.node_failed("free")


def test_legacy_facade_node_failed_empty_pool():
    svc = TenantProvisionService(4, policy="paper")
    for spec in paper_tenants():
        svc.register_spec(spec)
    svc.provision_idle()
    svc.node_failed("ws")          # ws owns nothing -> reattributed to st
    assert svc.total == 3 and svc.tenants["st"].alloc == 3
    assert svc.tenants["ws"].alloc == 0
    svc.check()


# ------------------------------------------------------ timeline downsample

def test_downsample_timeline_keeps_early_history():
    rows = [(float(i), i, 0, 0) for i in range(10_000)]
    out = downsample_timeline(rows, max_points=2000)
    assert len(out) <= 2001
    assert out[0] == rows[0]                     # early history preserved
    assert out[-1] == rows[-1]                   # final state preserved
    # strictly increasing, evenly strided
    times = [r[0] for r in out]
    assert times == sorted(times)
    short = [(0.0, 1, 2, 3)] * 50
    assert downsample_timeline(short, max_points=2000) == short


def test_simresult_timeline_is_downsampled_not_truncated(seed_world):
    jobs, ws = seed_world
    r = run_dynamic(jobs, ws, 160, horizon=2 * DAY)
    assert len(r.util_timeline) <= 2001
    # the first recorded event survives (the seed code truncated to the
    # LAST 2000 rows, losing early history)
    assert r.util_timeline[0][0] <= DAY / 10


# -------------------------------------------------- runtime orchestrator

class _StubTrainer:
    """Duck-typed ElasticTrainer: counts device moves, no JAX."""

    def __init__(self, model_size=2, global_batch=8):
        self.model_size = model_size
        self.global_batch = global_batch
        self.step = 0
        self.devices = []
        self.resizes = 0

    def start(self, devices):
        self.devices = list(devices)

    def resize(self, devices):
        self.devices = list(devices)
        self.resizes += 1


class _StubPool:
    """Duck-typed ServingPool: one replica per device."""

    def __init__(self):
        self.replicas = []

    def scale_to(self, devices):
        self.replicas = list(devices)

    def desired_replicas(self, load):
        return int(load)


def test_multitenant_orchestrator_routes_counts_to_devices():
    from repro.runtime.orchestrator import MultiTenantOrchestrator

    devices = [f"dev{i}" for i in range(16)]
    orch = MultiTenantOrchestrator(devices=devices, policy="demand_capped")
    ta, tb = _StubTrainer(model_size=2, global_batch=4), \
        _StubTrainer(model_size=2, global_batch=2)
    pa, pb = _StubPool(), _StubPool()
    orch.add_latency("ws-a", pa, priority=0)
    orch.add_latency("ws-b", pb, priority=1)
    orch.add_batch("hpc-a", ta, priority=2, weight=2.0)
    orch.add_batch("hpc-b", tb, priority=3)
    orch.start()
    # demand-capped: trainers get their max useful scale (tp*batch), rest free
    assert len(ta.devices) == 8 and len(tb.devices) == 4
    assert len(orch.devs.free) == 4
    orch.devs.check()

    # WS spike: ws-a wants 6 replicas -> 4 free + forced trainer shrink
    orch.latency_tick("ws-a", 6.0)
    assert len(pa.replicas) == 6
    assert len(ta.devices) + len(tb.devices) + len(pa.replicas) + \
        len(orch.devs.free) == 16
    orch.devs.check()
    orch.svc.check()
    # trainer shrank by whole DP groups (multiples of model_size)
    assert len(ta.devices) % ta.model_size == 0
    assert len(tb.devices) % tb.model_size == 0

    # second department preempts the first? no — ws-b is LOWER priority, so
    # it can only drain batch tenants, never ws-a
    orch.latency_tick("ws-b", 20.0)
    assert len(pa.replicas) == 6
    orch.devs.check()

    # load falls: replicas released, idle reflows to the trainers
    orch.latency_tick("ws-a", 0.0)
    orch.latency_tick("ws-b", 0.0)
    assert len(pa.replicas) == 0 and len(pb.replicas) == 0
    assert len(ta.devices) == 8 and len(tb.devices) == 4
    orch.devs.check()
    orch.svc.check()


class _ExclusiveTrainer(_StubTrainer):
    """Fails if it is given a device on which a serving replica still sits:
    a chip cannot hold both a replica's weights and a trainer's state."""

    def __init__(self, pools, **kw):
        super().__init__(**kw)
        self.pools = pools

    def _own(self, devices):
        shared = {d for p in self.pools for d in p.replicas} & set(devices)
        assert not shared, shared

    def start(self, devices):
        self._own(devices)
        super().start(devices)

    def resize(self, devices):
        self._own(devices)
        super().resize(devices)


@pytest.mark.parametrize("wiring", ["multitenant", "paper"])
def test_released_replicas_leave_before_the_trainer_arrives(wiring):
    from repro.runtime.orchestrator import MultiTenantOrchestrator
    devices = [f"dev{i}" for i in range(4)]
    pool = _StubPool()
    tr = _ExclusiveTrainer([pool], model_size=1, global_batch=12)
    if wiring == "multitenant":
        orch = MultiTenantOrchestrator(devices=devices, policy="slo_headroom")
        orch.add_latency("serve", pool, priority=0, floor=1)
        orch.add_batch("train", tr, priority=1, min_devices=2)
        orch.latency_tick("serve", 1.0)
        orch.start()
        tick = lambda n: orch.latency_tick("serve", float(n))  # noqa: E731
    else:
        # the paper's wiring: st (the trainer), then ws (the pool)
        orch = MultiTenantOrchestrator(devices=devices, policy="paper")
        orch.add_batch("st", tr, priority=1, min_devices=2)
        orch.add_latency("ws", pool, priority=0)
        orch.start()
        tick = lambda n: orch.latency_tick("ws", float(n))  # noqa: E731
    for want in (2, 1, 2, 0):
        tick(want)
        assert len(pool.replicas) == want
        assert len(tr.devices) + want <= 4
    assert tr.resizes >= 3


def test_multitenant_orchestrator_feeds_latency_signals_to_engine():
    """The runtime twin of the simulator's signal path: measured serving
    latency becomes TenantSignals headroom, and the slo_headroom engine
    drains the pool with the most headroom first."""
    from repro.runtime.orchestrator import MultiTenantOrchestrator

    devices = [f"dev{i}" for i in range(12)]
    orch = MultiTenantOrchestrator(devices=devices, policy="slo_headroom")
    hot, cozy = _StubPool(), _StubPool()
    tr = _StubTrainer(model_size=2, global_batch=2)
    orch.add_latency("ws-hot", hot, priority=0, floor=1)
    orch.add_latency("ws-cozy", cozy, priority=1, floor=1)
    orch.add_batch("hpc", tr, priority=2)
    orch.start()
    orch.latency_tick("ws-cozy", 4.0)
    assert len(cozy.replicas) == 4

    # real latency observations flow into the signals channel
    orch.observe_latency("ws-cozy", 0.5)
    sig = orch.svc.tenants["ws-cozy"].signals()
    assert sig.kind == "latency" and sig.alloc == 4
    assert sig.latency_headroom_s == 0.0    # no SLO autoscaler -> target 0

    # a hot claim bigger than free+trainer drains ws-cozy, but only down
    # to its floor
    orch.latency_tick("ws-hot", 11.0)
    assert len(cozy.replicas) >= 1
    assert len(hot.replicas) >= 8
    orch.devs.check()
    orch.svc.check()
    state = orch.svc.policy.state_snapshot()
    assert state["engine"] == "slo_headroom"
    assert "ws-cozy" in state["victim_nodes"]


# ------------------------------------------------------- property invariant

if not HAS_HYPOTHESIS:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_conservation_over_random_n_tenant_sequences():
        pass
else:
    @st.composite
    def tenant_sets(draw):
        n = draw(st.integers(2, 6))
        rows = []
        for i in range(n):
            kind = draw(st.sampled_from(["batch", "latency"]))
            rows.append((f"t{i}", kind, draw(st.integers(0, 5)),
                         draw(st.floats(0.0, 4.0))))
        if not any(k == "latency" for _, k, _, _ in rows):
            rows[0] = (rows[0][0], "latency", rows[0][2], rows[0][3])
        return rows

    @given(total=st.integers(10, 300),
           policy=st.sampled_from(sorted(POLICIES)),
           rows=tenant_sets(),
           ops=st.lists(
               st.tuples(st.sampled_from(["claim", "release", "demand",
                                          "fail", "repair"]),
                         st.integers(0, 5),      # tenant index
                         st.integers(0, 120)),   # amount
               max_size=60))
    @settings(max_examples=80, deadline=None)
    def test_conservation_over_random_n_tenant_sequences(
            total, policy, rows, ops):
        svc = TenantProvisionService(total, policy=policy)
        tenants = []
        for name, kind, prio, weight in rows:
            cb = (lambda k: lambda n: n)(kind)
            tenants.append(svc.register(Tenant(
                name, kind, priority=prio, weight=weight,
                on_force_release=cb if kind == "batch" else None)))
        repairs_due = 0
        for op, ti, n in ops:
            t = tenants[ti % len(tenants)]
            if op == "claim" and t.kind == "latency":
                got = svc.claim(t.name, n)
                assert 0 <= got <= n
            elif op == "release":
                svc.release(t.name, n)
            elif op == "demand" and t.kind == "batch":
                svc.set_demand(t.name, n)
            elif op == "fail":
                if svc.total > 0:
                    svc.node_failed(t.name)     # may reattribute
                    repairs_due += 1
                else:
                    with pytest.raises(ValueError):
                        svc.node_failed(t.name)
            elif op == "repair" and repairs_due > 0:
                svc.node_repaired()
                repairs_due -= 1
            # THE invariant: per-tenant allocations + free == total
            svc.check()
            assert sum(x.alloc for x in tenants) + svc.free == svc.total
            assert svc.free >= 0
            assert all(x.alloc >= 0 for x in tenants)
