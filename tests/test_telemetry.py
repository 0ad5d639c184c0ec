"""Control-plane telemetry tests (core/telemetry.py + repro.trace CLI).

Covers the PR-6 observability contract:

  * same-seed runs emit byte-identical traces (the determinism unit is
    ``Tracer.lines()`` — canonical JSONL);
  * a pinned golden trace for the paper engine on the degenerate
    two-tenant (2009) scenario — the exact causal story of §II-B's three
    rules (idle -> ST, WS claim forces ST release, release reflows);
  * causal-chain integrity (claim -> reclaim_plan -> reclaim_step,
    slo_violation -> slo_recovery) and schema validation;
  * the analyzer surface: summarize / diff / causality / validate /
    perfetto, plus the ``python -m repro.trace`` CLI;
  * no silent caps: the Tracer buffer and the MarketState ledgers count
    what they drop;
  * campaign integration: ``--trace`` spools an analyzable per-cell
    trace and folds a summary into the row WITHOUT changing any other
    row content (v5 artifact bit-exactness with tracing off).
"""
import json
import os

import pytest

from repro.core.simulator import ConsolidationSim
from repro.core.telemetry import (NULL_TRACER, Tracer, causality_report,
                                  check_causal_chains, diff_summaries,
                                  load_events, summarize_events,
                                  to_perfetto, validate_events)
from repro.core.traces import synthetic_sdsc_blue
from repro.core.types import (MarketState, Job, SimConfig, SLOConfig,
                              TenantSpec, paper_tenants)
from repro.serving.batching import ServiceTimeModel
from repro.workloads.arrivals import make_trace
from repro.workloads.autoscaler import RequestWorkload

HOUR = 3600.0


# ------------------------------------------------------------ scenarios

def paper_two_tenant_trace(metric_interval_s=600.0):
    """The degenerate 2009 two-tenant scenario, tiny and fully pinned."""
    jobs = [Job(job_id=0, submit_time=0.0, size=6, runtime=1200.0),
            Job(job_id=1, submit_time=300.0, size=4, runtime=900.0)]
    ws = [(0.0, 2), (600.0, 8), (1200.0, 3)]
    tr = Tracer(metric_interval_s=metric_interval_s)
    sim = ConsolidationSim(SimConfig(total_nodes=10, seed=0),
                           tenants=paper_tenants(jobs, ws),
                           horizon=1800.0, tracer=tr)
    sim.run()
    return tr


def request_level_trace(seed=3, policy="slo_headroom", tracer=None):
    """A consolidation cell with a request-level latency tenant (the
    deployment configuration): SLO autoscaler drives demand, reclaims
    fire, shortfall episodes open and close."""
    horizon = 2 * HOUR
    specs = [
        TenantSpec("ws", "latency", priority=0,
                   slo=SLOConfig(latency_target_s=1.0),
                   demand=RequestWorkload(
                       trace=make_trace("diurnal", 10.0, horizon,
                                        seed=seed),
                       model=ServiceTimeModel(),
                       slo=SLOConfig(latency_target_s=1.0))),
        TenantSpec("hpc", "batch", priority=1,
                   jobs=synthetic_sdsc_blue(seed=seed, n_jobs=60,
                                            horizon=horizon,
                                            max_nodes=12)),
    ]
    tr = tracer or Tracer()
    sim = ConsolidationSim(SimConfig(total_nodes=32, seed=seed),
                           horizon=horizon, tenants=specs, policy=policy,
                           tracer=tr)
    sim.run()
    return tr


# ------------------------------------------------------------- golden

GOLDEN_PAPER_TRACE = [
    '{"dropped_events": 0, "events": 16, "horizon": 1800.0, "policy": "paper", "seed": 0, "total_nodes": 10, "ts": 0.0, "type": "trace_header", "version": 1}',
    '{"nodes": 10, "tenant": "st", "ts": 0.0, "type": "idle_grant"}',
    '{"free": 0, "tenants": {"st": {"alloc": 10, "demand": 0, "headroom_s": 0.0, "queue_depth": 0, "spend": 0.0}, "ws": {"alloc": 0, "demand": 0, "headroom_s": 0.0, "queue_depth": 0, "spend": 0.0}}, "ts": 0.0, "type": "metrics"}',
    '{"demand": 2, "prev": 0, "source": "timeseries", "tenant": "ws", "ts": 0.0, "type": "autoscale"}',
    '{"deficit": 2, "engine": "paper", "parent": 1, "span": 2, "steps": [{"reason": "victim-chain", "take": 10, "victim": "st"}], "tenant": "ws", "ts": 0.0, "type": "reclaim_plan"}',
    '{"asked": 2, "claimant": "ws", "granted": 2, "parent": 2, "released": 2, "tenant": "st", "ts": 0.0, "type": "reclaim_step"}',
    '{"deficit": 2, "from_free": 0, "granted": 2, "requested": 2, "short": 0, "span": 1, "tenant": "ws", "ts": 0.0, "type": "claim"}',
    '{"demand": 8, "prev": 2, "source": "timeseries", "tenant": "ws", "ts": 600.0, "type": "autoscale"}',
    '{"deficit": 6, "engine": "paper", "parent": 3, "span": 4, "steps": [{"reason": "victim-chain", "take": 8, "victim": "st"}], "tenant": "ws", "ts": 600.0, "type": "reclaim_plan"}',
    '{"asked": 6, "claimant": "ws", "granted": 6, "parent": 4, "released": 6, "tenant": "st", "ts": 600.0, "type": "reclaim_step"}',
    '{"deficit": 6, "from_free": 0, "granted": 6, "requested": 6, "short": 0, "span": 3, "tenant": "ws", "ts": 600.0, "type": "claim"}',
    '{"free": 0, "tenants": {"st": {"alloc": 2, "demand": 0, "headroom_s": 0.0, "queue_depth": 1, "spend": 0.0}, "ws": {"alloc": 8, "demand": 8, "headroom_s": 0.0, "queue_depth": 0, "spend": 0.0}}, "ts": 600.0, "type": "metrics"}',
    '{"demand": 3, "prev": 8, "source": "timeseries", "tenant": "ws", "ts": 1200.0, "type": "autoscale"}',
    '{"nodes": 5, "tenant": "ws", "ts": 1200.0, "type": "release"}',
    '{"nodes": 5, "tenant": "st", "ts": 1200.0, "type": "idle_grant"}',
    '{"free": 0, "tenants": {"st": {"alloc": 7, "demand": 0, "headroom_s": 0.0, "queue_depth": 0, "spend": 0.0}, "ws": {"alloc": 3, "demand": 3, "headroom_s": 0.0, "queue_depth": 0, "spend": 0.0}}, "ts": 1200.0, "type": "metrics"}',
    '{"free": 0, "tenants": {"st": {"alloc": 7, "demand": 0, "headroom_s": 0.0, "queue_depth": 0, "spend": 0.0}, "ws": {"alloc": 3, "demand": 3, "headroom_s": 0.0, "queue_depth": 0, "spend": 0.0}}, "ts": 1800.0, "type": "metrics"}',
]


def test_golden_trace_paper_two_tenant():
    """The paper engine's causal story on the 2009 scenario is pinned
    line-for-line: rule 2 (all idle -> ST), rule 1/3 (WS claim plans and
    forces ST release), then WS release reflowing to ST."""
    assert paper_two_tenant_trace().lines() == GOLDEN_PAPER_TRACE


def test_same_seed_traces_identical():
    a = request_level_trace().lines()
    b = request_level_trace().lines()
    assert a == b
    assert len(a) > 100          # a real trace, not a stub


def test_trace_schema_and_chains_valid():
    tr = request_level_trace()
    events = [tr.header()] + tr.events
    assert validate_events(events) == []
    assert check_causal_chains(events) == []


# ------------------------------------------------------------ analysis

def test_summarize_counts_and_latency():
    tr = request_level_trace()
    events = [tr.header()] + tr.events
    s = summarize_events(events)
    assert s["events"] == len(events)
    from collections import Counter
    counted = Counter(e["type"] for e in events)
    assert s["by_type"] == dict(counted)
    # every traced claim either recovered (latency dist) or is counted
    rl = s["reclaim_latency_s"]
    n_claims = counted.get("claim", 0)
    assert rl["overall"]["n"] + sum(s["reclaim_latency_s"]
                                    ["unrecovered"].values()) <= n_claims
    assert rl["overall"]["p50"] <= rl["overall"]["p99"] \
        <= rl["overall"]["max"]


def test_causality_report_walks_chains():
    tr = request_level_trace()
    events = [tr.header()] + tr.events
    rep = causality_report(events, tenant="ws")
    assert rep["broken_chains"] == []
    assert rep["forced_claims"] == len(
        [e for e in events if e["type"] == "reclaim_plan"])
    for chain in rep["chains"]:
        assert chain["tenant"] == "ws"
        assert chain["planned_victims"], chain
        for drain in chain["drains"]:
            assert drain["released"] >= drain["granted"] >= 0


def test_diff_summaries_between_engines():
    full_a = request_level_trace(policy="paper")
    full_b = request_level_trace(policy="slo_headroom")
    sa = summarize_events([full_a.header()] + full_a.events)
    sb = summarize_events([full_b.header()] + full_b.events)
    d = diff_summaries(sa, sb)
    assert d["events"]["a"] == sa["events"]
    assert d["events"]["b"] == sb["events"]
    assert d["events"]["delta"] == sb["events"] - sa["events"]


def test_validate_catches_corruption():
    tr = paper_two_tenant_trace()
    events = [tr.header()] + tr.events
    missing = [dict(e) for e in events]
    del missing[4]["steps"]               # reclaim_plan loses its steps
    assert any("steps" in p for p in validate_events(missing))
    dangling = [dict(e) for e in events]
    dangling[5]["parent"] = 9999          # reclaim_step points nowhere
    problems = validate_events(dangling) + check_causal_chains(dangling)
    assert problems, "dangling parent must be reported"


def test_perfetto_export_structure():
    tr = request_level_trace()
    doc = to_perfetto([tr.header()] + tr.events)
    evs = doc["traceEvents"]
    assert evs, "empty perfetto export"
    phases = {e["ph"] for e in evs}
    assert "M" in phases          # thread metadata
    assert "C" in phases          # metric counters
    assert "i" in phases          # decision instants
    for e in evs:
        assert e.get("ts", 0) >= 0
        assert e["pid"] == 1


# ------------------------------------------------------- no silent caps

def test_tracer_buffer_cap_counts_drops():
    tr = Tracer(max_events=3)
    for i in range(10):
        tr.emit("release", tenant="t", nodes=1)
    assert len(tr.events) == 3
    assert tr.dropped_events == 7
    assert tr.header()["dropped_events"] == 7


def test_claim_path_counts_drops_at_cap():
    """The inlined hot-path emits in claim() honor the cap too."""
    from repro.core.policies import Tenant
    from repro.core.provision import TenantProvisionService
    tr = Tracer(max_events=0)
    svc = TenantProvisionService(8, policy="paper", tracer=tr)
    svc.register(Tenant("st", "batch", priority=1,
                        on_force_release=lambda n: n))
    svc.register(Tenant("ws", "latency", priority=0))
    svc.provision_idle()
    svc.claim("ws", 4)
    assert tr.events == []
    assert tr.dropped_events >= 3  # idle_grant + plan + step + claim


def test_market_ledger_caps_are_recorded():
    from repro.core.types import MARKET_SAMPLES_MAX
    m = MarketState()
    m.register("t", 1e9)
    for i in range(MARKET_SAMPLES_MAX + 5):
        m.debit("t", 1, 1.0, "idle", interval=i)
        m.note_price(1.0)
    snap = m.snapshot()
    assert snap["dropped_entries"]["ledger"] == 5
    assert snap["dropped_entries"]["clearing_prices"] == 5
    assert len(m.ledger) == MARKET_SAMPLES_MAX


def test_market_debit_lands_in_trace_past_ledger_cap():
    """Every debit reaches the tracer even after the inspection-sample
    cap — the trace is the uncapped record."""
    tr = Tracer()
    m = MarketState(tracer=tr)
    m.register("t", 1e9)
    from repro.core.types import MARKET_SAMPLES_MAX
    n = MARKET_SAMPLES_MAX + 3
    for i in range(n):
        m.debit("t", 1, 1.0, "idle", interval=i)
    debits = [e for e in tr.events if e["type"] == "debit"]
    assert len(debits) == n


# ------------------------------------------------------- off-by-default

def test_null_tracer_is_disabled_and_shared():
    assert NULL_TRACER.enabled is False
    NULL_TRACER.emit("claim", tenant="x")       # must be a no-op
    assert NULL_TRACER.events == []
    sim_tr = ConsolidationSim(SimConfig(total_nodes=4, seed=0),
                              tenants=paper_tenants(),
                              horizon=10.0).tracer
    assert sim_tr is NULL_TRACER


# ----------------------------------------------------------- round trip

def test_jsonl_round_trip(tmp_path):
    tr = paper_two_tenant_trace()
    path = str(tmp_path / "t.trace.jsonl")
    tr.to_jsonl(path)
    events = load_events(path)
    assert [json.dumps(e, sort_keys=True, default=float)
            for e in events] == tr.lines()


def test_trace_cli(tmp_path):
    from repro.trace import main
    tr = request_level_trace()
    path = str(tmp_path / "cell.trace.jsonl")
    tr.to_jsonl(path)
    out = str(tmp_path / "cell.perfetto.json")
    assert main(["validate", path]) == 0
    assert main(["summarize", path]) == 0
    assert main(["summarize", path, "--json"]) == 0
    assert main(["causality", path, "--tenant", "ws"]) == 0
    assert main(["diff", path, path]) == 0
    assert main(["perfetto", path, "--out", out]) == 0
    assert json.load(open(out))["traceEvents"]
    # a corrupted trace must fail validation with a non-zero exit
    bad = str(tmp_path / "bad.trace.jsonl")
    events = load_events(path)
    del events[-1]["type"]
    with open(bad, "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")
    assert main(["validate", bad]) == 1


# ------------------------------------------------------------ runtime

def test_orchestrator_emits_autoscale_decisions():
    from repro.runtime.orchestrator import MultiTenantOrchestrator

    class _Pool:
        def __init__(self):
            self.replicas = []

        def scale_to(self, devices):
            self.replicas = list(devices)

        def desired_replicas(self, load):
            return int(load)

    class _Trainer:
        model_size, global_batch = 2, 4

        def __init__(self):
            self.devices = []
            self.step = 0

        def start(self, devices):
            self.devices = list(devices)

        def resize(self, devices):
            self.devices = list(devices)

    tr = Tracer()
    orch = MultiTenantOrchestrator(devices=[f"d{i}" for i in range(12)],
                                   policy="demand_capped", tracer=tr)
    orch.add_latency("ws", _Pool(), priority=0)
    orch.add_batch("hpc", _Trainer(), priority=1)
    orch.start()
    orch.latency_tick("ws", 6.0)
    orch.latency_tick("ws", 0.0)
    kinds = [e["type"] for e in tr.events]
    assert "autoscale" in kinds
    assert "claim" in kinds
    scale = [e for e in tr.events if e["type"] == "autoscale"]
    assert all(e["source"] in ("slo_autoscaler", "utilization")
               for e in scale)
    # ticks are the runtime's clock: timestamps are monotone
    ts = [e["ts"] for e in tr.events]
    assert ts == sorted(ts)
    assert validate_events([tr.header()] + tr.events) == []


# ------------------------------------------------------------ campaign

VOLATILE_METRICS = ("wall_s", "queue_sim_s")


def _strip_volatile(row):
    row = json.loads(json.dumps(row))          # deep copy
    for k in VOLATILE_METRICS:
        row["metrics"].pop(k, None)
    row.pop("queue_sim", None)
    row.pop("trace_file", None)
    row.pop("trace_summary", None)
    return row


def test_campaign_trace_flag_and_bit_exactness(tmp_path):
    """--trace spools an analyzable per-cell trace and folds a summary
    into the row; every OTHER row key is byte-identical to the untraced
    run (the v5 artifact contract)."""
    from repro.workloads.campaign import ScenarioCell, run_cell
    cell = ScenarioCell(preempt="kill", scheduler="first_fit",
                        arrival="poisson", total_nodes=24,
                        slo_target_s=30.0, horizon_s=1800.0,
                        n_jobs=10, rate_rps=1.0, mix="2hpc2ws",
                        policy="slo_headroom")
    plain = run_cell(cell)
    traced = run_cell(cell, trace_dir=str(tmp_path))
    assert "trace_file" not in plain
    assert os.path.exists(traced["trace_file"])
    # filename contract: cell_key (collision-proof hash), not cell_id
    assert os.path.basename(traced["trace_file"]) \
        == f"{cell.cell_key()}.trace.jsonl"
    assert traced["trace_summary"]["events"] > 0
    assert _strip_volatile(plain) == _strip_volatile(traced)
    # the spooled trace is analyzable and causally intact
    events = load_events(traced["trace_file"])
    assert validate_events(events) == []
    assert check_causal_chains(events) == []
    assert events[0]["cell_id"] == cell.cell_id()
    assert events[0]["schema"]
