"""Request-level WS workload subsystem: arrivals, queueing, autoscaler,
campaign, and the simulator integration."""
import numpy as np
import pytest

from repro.core.simulator import ConsolidationSim
from repro.core.traces import synthetic_sdsc_blue
from repro.core.types import (Request, SimConfig, SLOConfig, WSDemandProvider,
                              paper_tenants)
from repro.serving.batching import ContinuousBatcher, ServiceTimeModel
from repro.serving.batching import Request as BatchRequest
from repro.workloads import (RequestWorkload, SLOAutoscaler, burstiness_index,
                             capacity_steps, make_trace, simulate_queue)
from repro.workloads.campaign import (METRIC_KEYS, ScenarioCell, make_grid,
                                      reduce_metrics, run_campaign, run_cell)

HOUR = 3600.0
MODEL = ServiceTimeModel()
SLO = SLOConfig(latency_target_s=30.0)


# ------------------------------------------------------------- arrivals


@pytest.mark.parametrize("kind", ["poisson", "mmpp", "diurnal",
                                  "flash_crowd"])
def test_arrivals_deterministic_and_sorted(kind):
    a = make_trace(kind, 2.0, HOUR, seed=3)
    b = make_trace(kind, 2.0, HOUR, seed=3)
    np.testing.assert_array_equal(a.t, b.t)
    np.testing.assert_array_equal(a.decode_tokens, b.decode_tokens)
    assert np.all(np.diff(a.t) >= 0)
    assert a.t[-1] < HOUR and a.t[0] >= 0
    assert len(a.prompt_tokens) == len(a.t) == len(a.decode_tokens)
    assert a.prompt_tokens.min() >= 1 and a.decode_tokens.min() >= 1


def test_poisson_rate_and_dispersion_within_tolerance():
    # long window so the estimators concentrate
    tr = make_trace("poisson", 5.0, 6 * HOUR, seed=0)
    rate = len(tr) / (6 * HOUR)
    assert rate == pytest.approx(5.0, rel=0.05)
    # Poisson: index of dispersion ~ 1
    assert 0.8 < burstiness_index(tr, window_s=60.0) < 1.3


def test_mmpp_burstier_than_poisson():
    poi = make_trace("poisson", 2.0, 6 * HOUR, seed=1)
    mmpp = make_trace("mmpp", 2.0, 6 * HOUR, seed=1)
    assert burstiness_index(mmpp) > 3.0 * burstiness_index(poi)
    # mean rate between the lo and hi modulated rates
    rate = len(mmpp) / (6 * HOUR)
    assert 0.4 * 2.0 < rate < 1.6 * 2.0


def test_flash_crowd_adds_spikes_over_base():
    base = make_trace("diurnal", 2.0, 6 * HOUR, seed=2)
    flash = make_trace("flash_crowd", 2.0, 6 * HOUR, seed=2)
    assert len(flash) > len(base)
    assert burstiness_index(flash) > 5.0


def test_trace_to_requests_roundtrip():
    tr = make_trace("poisson", 1.0, 600.0, seed=0)
    reqs = tr.to_requests()
    assert all(isinstance(r, Request) for r in reqs)
    assert [r.arrival for r in reqs] == list(tr.t)
    assert reqs[0].latency is None


# ------------------------------------------------------------- queueing


def test_queue_no_contention_latency_equals_service():
    tr = make_trace("poisson", 0.5, HOUR, seed=0)
    m = simulate_queue(tr, [(0.0, 1000)], MODEL, SLO)
    svc = MODEL.service_times(tr.prompt_tokens, tr.decode_tokens)
    assert m.mean_wait_s == pytest.approx(0.0, abs=1e-9)
    assert m.mean_s == pytest.approx(float(svc.mean()), rel=1e-6)
    assert m.n_served == len(tr)


def test_queue_undersized_cluster_builds_backlog():
    tr = make_trace("poisson", 2.0, HOUR, seed=0)
    small = simulate_queue(tr, [(0.0, 1)], MODEL, SLO)
    big = simulate_queue(tr, [(0.0, 50)], MODEL, SLO)
    assert small.p99_s > big.p99_s
    assert small.violation_rate > big.violation_rate
    assert not small.slo_met and big.slo_met


def test_queue_zero_capacity_counts_unserved():
    tr = make_trace("poisson", 1.0, 600.0, seed=0)
    m = simulate_queue(tr, [(0.0, 0)], MODEL, SLO, horizon=600.0)
    assert m.unserved == len(tr)
    assert m.violation_rate == 1.0 and not m.slo_met


def test_queue_capacity_rise_rescues_waiting_requests():
    tr = make_trace("poisson", 1.0, 600.0, seed=0)
    # no capacity for 300 s, then plenty: everything queued at t<300 starts
    # at 300 and still finishes
    m = simulate_queue(tr, [(0.0, 0), (300.0, 100)], MODEL, SLO)
    assert m.unserved == 0
    early = tr.t < 300.0
    assert m.mean_wait_s > 0


def test_capacity_steps_normalizes_events():
    t, k = capacity_steps([(5.0, 2), (0.0, 1), (5.0, 3)], slots_per_node=4)
    assert list(t) == [0.0, 5.0]
    assert list(k) == [4, 12]          # last level at t=5 wins, x4 slots


def test_batcher_round_time_matches_model():
    model = ServiceTimeModel(prefill_tokens_per_s=1000.0,
                             decode_tokens_per_s=100.0,
                             batch_interference=0.1, max_batch=4)
    b = ContinuousBatcher(max_batch=4)
    reqs = [BatchRequest(i, np.zeros(50, np.int32), 20) for i in range(2)]
    t = b.estimate_round_time(reqs, model)
    # 2 * 50 / 1000 prefill + 20 * 1.1 / 100 decode
    assert t == pytest.approx(0.1 + 0.22)


# ------------------------------------------------------------ autoscaler


def test_autoscaler_scales_with_rate_and_slo():
    asc = SLOAutoscaler(MODEL, SLO)
    svc_mean, svc_p99 = 8.0, 20.0
    lo = asc.desired_nodes(1.0, svc_mean, 0.3, svc_p99)
    hi = asc.desired_nodes(10.0, svc_mean, 0.3, svc_p99)
    assert hi > lo >= 1
    tight = SLOAutoscaler(MODEL, SLOConfig(latency_target_s=21.0))
    loose = SLOAutoscaler(MODEL, SLOConfig(latency_target_s=120.0))
    assert tight.desired_nodes(10.0, svc_mean, 0.3, svc_p99) >= \
        loose.desired_nodes(10.0, svc_mean, 0.3, svc_p99)


def test_autoscaler_infeasible_slo_provisions_for_zero_queueing():
    asc = SLOAutoscaler(MODEL, SLOConfig(latency_target_s=5.0))
    n = asc.desired_nodes(10.0, 8.0, 0.3, p99_service_s=20.0)
    # service alone busts the target: still provisions ~offered load
    offered_nodes = 10.0 * 8.0 / MODEL.slots_per_replica
    assert n >= offered_nodes
    assert n < 10 * offered_nodes


def test_workload_provider_plan_meets_slo_when_granted():
    tr = make_trace("flash_crowd", 1.5, 2 * HOUR, seed=0)
    ws = RequestWorkload(trace=tr, model=MODEL, slo=SLO)
    assert isinstance(ws, WSDemandProvider)
    ev = ws.demand_events(2 * HOUR)
    assert ev and all(n >= 0 for _, n in ev)
    m = ws.planned_metrics(2 * HOUR)
    assert m["slo_met"]
    assert m["p99_s"] <= SLO.latency_target_s


# --------------------------------------------------- simulator integration


def test_consolidation_sim_with_request_workload():
    tr = make_trace("poisson", 1.5, 2 * HOUR, seed=0)
    ws = RequestWorkload(trace=tr, model=MODEL, slo=SLO)
    jobs = synthetic_sdsc_blue(seed=0, n_jobs=60, horizon=2 * HOUR,
                               max_nodes=32)
    cfg = SimConfig(total_nodes=64)
    res = ConsolidationSim(cfg, tenants=paper_tenants(jobs, ws),
                           horizon=2 * HOUR).run()
    assert res.ws_latency is not None
    assert res.ws_latency["n_requests"] == len(tr)
    # WS has strict priority and the cluster is big enough: SLO holds
    assert res.ws_unmet_node_seconds == 0.0
    assert res.ws_latency["slo_met"]
    assert res.completed > 0


def test_consolidation_sim_request_workload_deterministic():
    tr = make_trace("mmpp", 1.0, HOUR, seed=4)
    jobs = synthetic_sdsc_blue(seed=4, n_jobs=40, horizon=HOUR,
                               max_nodes=16)
    outs = []
    for _ in range(2):
        ws = RequestWorkload(trace=tr, model=MODEL, slo=SLO)
        res = ConsolidationSim(SimConfig(total_nodes=48),
                               tenants=paper_tenants(jobs, ws),
                               horizon=HOUR).run()
        outs.append((res.completed, res.ws_latency["p99_s"]))
    assert outs[0] == outs[1]


def test_node_fail_accounting_stays_consistent():
    """Satellite fix: ST node loss routes through STServer, so the CMS's
    alloc and the service's st record can never diverge — audited at
    every event."""
    tr = make_trace("poisson", 0.5, 2 * HOUR, seed=5)
    ws = RequestWorkload(trace=tr, model=MODEL, slo=SLO)
    jobs = synthetic_sdsc_blue(seed=5, n_jobs=80, horizon=2 * HOUR,
                               max_nodes=32)
    cfg = SimConfig(total_nodes=48, node_mtbf=20 * HOUR,
                    node_repair_time=600.0)
    sim = ConsolidationSim(cfg, tenants=paper_tenants(jobs, ws),
                           horizon=2 * HOUR)
    orig = sim._account
    st_cms = sim._rt_by_name["st"].server
    ws_cms = sim._rt_by_name["ws"].server
    st_rec, ws_rec = sim.svc.tenants["st"], sim.svc.tenants["ws"]

    def audited(t):
        orig(t)
        sim.svc.check()
        assert st_cms.alloc == st_rec.alloc, (st_cms.alloc, st_rec.alloc)
        assert ws_cms.alloc == ws_rec.alloc
        assert st_cms.used <= st_cms.alloc

    sim._account = audited
    res = sim.run()
    assert res.submitted == 80


def test_node_fail_prefers_idle_over_eviction():
    """Idle ST nodes absorb a node loss before any job is evicted."""
    from repro.core.st_cms import STServer
    st = STServer(SimConfig(), lambda j, t: None, lambda j: None)
    st.grant(10, 0.0)
    from repro.core.types import Job
    j = Job(job_id=1, submit_time=0.0, size=4, runtime=100.0)
    st.submit(j, 0.0)
    assert st.idle == 6
    st.node_lost(1.0)
    assert st.alloc == 9 and len(st.running) == 1       # no eviction
    for _ in range(5):
        st.node_lost(2.0)
    assert st.alloc == 4
    st.node_lost(3.0)                                    # now a job must die
    assert st.alloc == 3 and len(st.running) == 0


# -------------------------------------------------------------- campaign


def test_campaign_tiny_grid_shape():
    cells = make_grid("tiny")
    assert len(cells) >= 8
    assert len({c.cell_id() for c in cells}) == len(cells)


def test_campaign_cell_and_reduction(tmp_path):
    cells = [ScenarioCell(preempt=p, scheduler="first_fit",
                          arrival="poisson", total_nodes=48,
                          slo_target_s=30.0, horizon_s=1800.0, n_jobs=20,
                          rate_rps=1.0)
             for p in ("kill", "checkpoint")]
    out = tmp_path / "campaign.json"
    art = run_campaign(cells, workers=1, out_path=str(out),
                       grid_name="unit")
    assert out.exists()
    assert art["n_cells"] == 2
    for r in art["cells"]:
        assert set(METRIC_KEYS) <= set(r["metrics"])
    red = art["reductions"]
    assert "overall" in red and "by_preempt" in red
    assert red["overall"]["cells"] == 2
    import json
    disk = json.loads(out.read_text())
    assert disk["schema"] == "phoenix-campaign-v7"
    assert disk["throughput"]["queue_requests_per_s"] > 0
