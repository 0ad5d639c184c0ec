"""Fault-injection subsystem (core/faults.py): degenerate bit-for-bit
compatibility, policy-axis-independent fault streams, correlated rack
blasts, flapping nodes, drain windows and fail/repair pairing."""
import pytest

from repro.core.faults import (FAULT_PROFILES, FaultSpec, fault_rng,
                               get_fault_spec)
from repro.core.nodes import DRAIN_POOL, NodeInventory, NodeState
from repro.core.policies import Tenant
from repro.core.provision import TenantProvisionService
from repro.core.simulator import ConsolidationSim
from repro.core.telemetry import (Tracer, check_causal_chains,
                                  summarize_events, validate_events)
from repro.core.traces import synthetic_sdsc_blue
from repro.core.types import SimConfig, TenantSpec, paper_tenants

DAY = 86400.0
HORIZON = 7200.0


def _mini_tenants(seed=0):
    jobs_a = synthetic_sdsc_blue(seed=seed, n_jobs=30, horizon=HORIZON,
                                 max_nodes=24)
    jobs_b = synthetic_sdsc_blue(seed=seed + 7, n_jobs=30, horizon=HORIZON,
                                 max_nodes=24)
    dem_a = [(t * 300.0, 10 + (t % 4) * 6) for t in range(24)]
    dem_b = [(t * 240.0, 8 + (t % 3) * 5) for t in range(30)]
    return [
        TenantSpec("ws-0", "latency", priority=0, demand=dem_a),
        TenantSpec("ws-1", "latency", priority=1, demand=dem_b),
        TenantSpec("hpc-0", "batch", priority=2, jobs=jobs_a),
        TenantSpec("hpc-1", "batch", priority=3, weight=0.5, jobs=jobs_b),
    ]


def _run_traced(profile, policy="paper", seed=0, total=64):
    tr = Tracer()
    cfg = SimConfig(total_nodes=total, seed=seed,
                    faults=get_fault_spec(profile))
    sim = ConsolidationSim(cfg, horizon=HORIZON, tenants=_mini_tenants(seed),
                           policy=policy, tracer=tr)
    res = sim.run()
    return sim, res, tr


def _fault_seq(tr):
    return [(e["ts"], e["node"]) for e in tr.events
            if e["type"] == "node_fail"]


# ------------------------------------------------ degenerate bit-for-bit

def test_independent_profile_reproduces_legacy_mtbf_bit_for_bit():
    """FaultSpec('independent', seed=None) IS the legacy node_mtbf path:
    same shared RNG stream, same draw order, same pool-proportional
    attribution — identical results down to the util timeline."""
    def run(cfg):
        jobs = synthetic_sdsc_blue(seed=3, n_jobs=120, horizon=2 * DAY,
                                   max_nodes=64)
        dem = [(t * 600.0, 20 + (t % 7) * 5) for t in range(200)]
        return ConsolidationSim(cfg, tenants=paper_tenants(jobs, dem),
                                horizon=2 * DAY).run()

    legacy = run(SimConfig(total_nodes=160, node_mtbf=50 * DAY,
                           node_repair_time=3600.0, seed=3))
    spec = run(SimConfig(total_nodes=160, seed=3,
                         faults=FaultSpec(profile="independent",
                                          mtbf_s=50 * DAY,
                                          repair_time_s=3600.0)))
    for k in ("completed", "killed", "avg_turnaround", "st_avg_alloc",
              "ws_avg_alloc", "ws_unmet_node_seconds"):
        assert getattr(legacy, k) == getattr(spec, k), k
    assert legacy.util_timeline == spec.util_timeline


# -------------------------------------------- policy-axis determinism

# pinned fault sequences for seed=0, 64 nodes, 7200 s, _mini_tenants:
# regenerate ONLY if the fault-stream contract (fault_rng seeding or
# victim selection over up_ids) deliberately changes
PINNED_FIRST3 = {
    "rack_corr": [(597.7305059015397, 61), (597.7305059015397, 48),
                  (597.7305059015397, 49)],
    "flapping": [(1023.4472573226027, 46), (1193.5976445022438, 46),
                 (1392.1563694393838, 20)],
}


@pytest.mark.parametrize("profile", ["rack_corr", "flapping"])
def test_fault_sequence_pinned_and_policy_independent(profile):
    """Changing --policy (or any allocation knob) must not perturb the
    injected (ts, node) fault sequence within a cell: injectors draw from
    an isolated stream and select victims over the inventory's up set,
    which only past faults can change."""
    seqs = {}
    for policy in ("paper", "slo_headroom", "budget_auction"):
        _, _, tr = _run_traced(profile, policy=policy)
        seqs[policy] = _fault_seq(tr)
    ref = seqs["paper"]
    assert ref[:3] == PINNED_FIRST3[profile]
    for policy, seq in seqs.items():
        assert seq == ref, policy


def test_fault_rng_isolated_from_sim_stream():
    spec = get_fault_spec("rack_corr")
    a = fault_rng(spec, 42).random()
    b = fault_rng(spec, 42).random()
    c = fault_rng(spec, 43).random()
    d = fault_rng(get_fault_spec("flapping"), 42).random()
    assert a == b          # deterministic in (profile, seed)
    assert a != c          # seed-sensitive
    assert a != d          # profile-namespaced


def test_get_fault_spec_rejects_unknown_profile():
    with pytest.raises(ValueError, match="unknown fault profile"):
        get_fault_spec("meteor_strike")
    assert get_fault_spec("none") is None
    assert set(FAULT_PROFILES) >= {"none", "independent", "rack_corr",
                                   "flapping"}


# ----------------------------------------------------- injector behavior

def test_rack_blast_victims_cluster_in_one_domain():
    sim, _, tr = _run_traced("rack_corr")
    fails = [e for e in tr.events if e["type"] == "node_fail"]
    assert fails
    rack = sim.inventory.rack_size
    by_ts = {}
    for e in fails:
        by_ts.setdefault(e["ts"], []).append(e["node"])
    blasts = [nodes for nodes in by_ts.values() if len(nodes) > 1]
    assert blasts, "expected at least one multi-node blast"
    for nodes in blasts:
        assert len({n // rack for n in nodes}) == 1, nodes
        assert len(nodes) <= get_fault_spec("rack_corr").blast_radius


def test_flapping_nodes_cycle_and_stay_flappers():
    sim, _, tr = _run_traced("flapping")
    fails = [e for e in tr.events if e["type"] == "node_fail"]
    repairs = [e for e in tr.events if e["type"] == "node_repair"]
    assert fails and all(e["cause"] == "flap" for e in fails)
    # only designated flappers ever fail, and they fail repeatedly
    flappers = {n.id for n in sim.inventory.nodes if n.flapper}
    assert {e["node"] for e in fails} <= flappers
    assert len(fails) > len(flappers) - len(FAULT_PROFILES)
    # a repaired flapper returns to FLAPPING, never HEALTHY
    repaired = {e["node"] for e in repairs}
    for nid in repaired:
        assert sim.inventory.state_of(nid) in (NodeState.FLAPPING,
                                               NodeState.REPAIRING)
    back_up = [e for e in tr.events if e["type"] == "node_state"
               and e["from"] == "repairing"]
    assert back_up and all(e["to"] == "flapping" for e in back_up)


def test_suppressed_faults_traced_and_repairs_never_overshoot():
    """Satellite: when the cluster is at its one-node minimum a fault is
    traced as fault_suppressed (not silently dropped), consumes no victim
    draw, schedules no repair — so fail/repair events stay paired and
    node_repaired can never push total past the configured size."""
    tr = Tracer()
    cfg = SimConfig(total_nodes=2, seed=1,
                    faults=FaultSpec(profile="independent", mtbf_s=300.0,
                                     repair_time_s=50_000.0))
    jobs = synthetic_sdsc_blue(seed=1, n_jobs=5, horizon=HORIZON,
                               max_nodes=2)
    sim = ConsolidationSim(cfg, tenants=paper_tenants(jobs, [(0.0, 1)]),
                           horizon=HORIZON, tracer=tr)
    sim.run()
    s = summarize_events([tr.header()] + tr.events)["faults"]
    assert s["suppressed"] > 0
    assert s["failures"] == 1          # every later fault was suppressed
    assert s["failures"] - s["repairs"] == 2 - sim.svc.total
    assert sim.svc.total >= 1
    assert validate_events([tr.header()] + tr.events) == []


def test_fail_repair_spans_pair_causally():
    _, _, tr = _run_traced("independent")
    evs = [tr.header()] + tr.events
    assert validate_events(evs) == []
    assert check_causal_chains(evs) == []
    fails = {e["span"]: e for e in tr.events if e["type"] == "node_fail"}
    repairs = [e for e in tr.events if e["type"] == "node_repair"]
    assert repairs
    for r in repairs:
        parent = fails[r["parent"]]            # KeyError = orphaned repair
        assert parent["node"] == r["node"]     # same node, same outage


# -------------------------------------------------------- drain windows

def _drained_service(drain_s=30.0):
    """Service + inventory with a manual drain scheduler: the test owns
    the clock and fires drain completions explicitly."""
    fired = []
    svc = TenantProvisionService(12, policy="paper", tracer=Tracer())
    inv = NodeInventory(12)
    svc.attach_inventory(inv)
    svc.configure_drain(drain_s, lambda dt, fn: fired.append((dt, fn)))
    st = svc.register(Tenant("st", "batch", priority=1))
    svc.register(Tenant("ws", "latency", priority=0))
    st.on_force_release = lambda n: n
    svc.provision_idle()                       # all 12 -> st
    return svc, inv, fired


def test_drain_window_delays_claimant_credit():
    svc, inv, fired = _drained_service()
    got = svc.claim("ws", 5)
    # reclaimed nodes sit in the drain pool: the claim returns only what
    # was granted immediately (free pool), the rest is pending
    assert got == 0
    assert svc.draining == 5 and svc.tenants["ws"].alloc == 0
    assert inv.pool(DRAIN_POOL) == [0, 1, 2, 3, 4]
    assert all(inv.state_of(i) is NodeState.DRAINING for i in range(5))
    inv.audit(svc)
    (dt, fn), = fired
    assert dt == 30.0
    fn()                                       # drain window elapses
    assert svc.draining == 0 and svc.tenants["ws"].alloc == 5
    assert inv.pool("ws") == [0, 1, 2, 3, 4]
    inv.audit(svc)
    # causal chain: drain_complete parents the reclaim_step's span
    evs = svc.tracer.events
    step = next(e for e in evs if e["type"] == "reclaim_step")
    done = next(e for e in evs if e["type"] == "drain_complete")
    assert done["parent"] == step["span"]
    assert done["nodes"] == 5
    assert check_causal_chains([svc.tracer.header()] + evs) == []


def test_drain_node_failure_credits_only_survivors():
    svc, inv, fired = _drained_service()
    svc.claim("ws", 4)
    assert svc.draining == 4
    svc.drain_node_failed(1, cause="rack_blast")   # dies mid-drain
    assert svc.draining == 3 and svc.total == 11
    (dt, fn), = fired
    fn()
    # only the 3 survivors reach the claimant; the dead node is down
    assert svc.tenants["ws"].alloc == 3
    assert inv.pool("ws") == [0, 2, 3]
    assert inv.state_of(1) is NodeState.REPAIRING
    inv.audit(svc)
    svc.node_repaired(node=1)
    assert svc.total == 12
    inv.audit(svc)


def test_sim_level_drain_time_slows_ws_recovery():
    """The same scenario with a drain window must deliver reclaimed nodes
    to WS strictly later (more unmet node-seconds, never less)."""
    def run(drain_s):
        jobs = synthetic_sdsc_blue(seed=2, n_jobs=40, horizon=HORIZON,
                                   max_nodes=48)
        dem = [(t * 600.0, 10 + (t % 3) * 15) for t in range(12)]
        cfg = SimConfig(total_nodes=64, seed=2, drain_time_s=drain_s,
                        faults=FaultSpec(profile="independent", mtbf_s=0.0))
        return ConsolidationSim(cfg, tenants=paper_tenants(jobs, dem),
                                horizon=HORIZON).run()

    instant = run(0.0)
    drained = run(120.0)
    assert drained.ws_unmet_node_seconds > instant.ws_unmet_node_seconds
    assert sum(drained.policy_state["victim_nodes"].values()) > 0


# ------------------------------------------------------- campaign axis

def test_campaign_fault_axis_changes_cell_identity():
    from repro.workloads.campaign import ScenarioCell
    base = dict(preempt="kill", scheduler="first_fit", arrival="poisson",
                total_nodes=96, slo_target_s=30.0)
    plain = ScenarioCell(**base)
    faulty = ScenarioCell(**base, fault_profile="rack_corr")
    assert plain.cell_key() != faulty.cell_key()
    assert plain.cell_id() != faulty.cell_id()
    assert "frack_corr" in faulty.cell_id()
    assert "fnone" not in plain.cell_id()      # default stays unadorned


def test_campaign_traced_cell_with_faults_validates():
    from repro.workloads.campaign import ScenarioCell, run_cell
    import json, os, tempfile
    cell = ScenarioCell(preempt="kill", scheduler="first_fit",
                        arrival="poisson", total_nodes=48,
                        slo_target_s=30.0, horizon_s=1800.0, n_jobs=16,
                        rate_rps=1.0, policy="slo_headroom", mix="2hpc2ws",
                        fault_profile="rack_corr")
    with tempfile.TemporaryDirectory() as td:
        row = run_cell(cell, trace_dir=td)
        assert row["fault_profile"] == "rack_corr"
        faults = row["trace_summary"]["faults"]
        assert faults["failures"] > 0
        with open(row["trace_file"]) as f:
            evs = [json.loads(line) for line in f]
        assert validate_events(evs) == []
        assert check_causal_chains(evs) == []
