"""Unit tests for the paper's provisioning / CMS policies (§II-B)."""
import pytest

from repro.core.provision import TenantProvisionService
from repro.core.st_cms import STServer
from repro.core.types import (Job, JobState, SimConfig, SLOConfig,
                              paper_tenants)
from repro.core.ws_cms import WSServer, demand_from_load

import numpy as np


def make_st(cfg=None):
    finishes = []
    st = STServer(cfg or SimConfig(), lambda j, t: finishes.append((j, t)),
                  lambda j: None)
    return st, finishes


def paper_service(total, **hooks):
    """The paper's service: st then ws under the paper policy; ``hooks``
    are the st department's on_grant/on_force_release."""
    svc = TenantProvisionService(total, policy="paper")
    st_spec, ws_spec = paper_tenants()
    svc.register_spec(st_spec, **hooks)
    svc.register_spec(ws_spec)
    return svc, svc.tenants["st"], svc.tenants["ws"]


def test_provision_idle_goes_to_st():
    granted = []
    svc, st, _ = paper_service(100, on_grant=granted.append)
    svc.provision_idle()
    assert st.alloc == 100 and svc.free == 0 and granted == [100]


def test_ws_priority_forces_st_release():
    released = []

    def force(n):
        released.append(n)
        return n

    svc, st, ws = paper_service(10, on_force_release=force)
    svc.provision_idle()
    got = svc.claim("ws", 4)
    assert got == 4 and released == [4]
    assert ws.alloc == 4 and st.alloc == 6
    svc.check()


def test_ws_release_reprovisions_to_st():
    svc, st, ws = paper_service(10, on_force_release=lambda n: n)
    svc.provision_idle()
    svc.claim("ws", 5)
    assert ws.alloc == 5
    svc.release("ws", 3)
    # released nodes must flow straight back to ST (rule 2)
    assert svc.free == 0 and st.alloc == 8 and ws.alloc == 2


def test_kill_order_min_size_then_shortest_running():
    st, _ = make_st()
    st.grant(16, now=0.0)   # exactly 8+4+4: no idle to absorb the reclaim
    jobs = [Job(1, 0.0, 8, 1000.0), Job(2, 0.0, 4, 1000.0),
            Job(3, 0.0, 4, 1000.0)]
    st.submit(jobs[0], 0.0)
    st.submit(jobs[1], 0.0)   # starts at t=0
    # make job 3 start later => shorter running time at kill
    st.submit(jobs[2], 0.0)
    # all three fit (8+4+4=16 <= 20); simulate kill at t=10 after j3
    # restarted at t=5
    jobs[2].start_time = 5.0
    st.force_release(2, now=10.0)
    # min size is 4 (jobs 2,3); shortest running = job 3 (started at 5)
    assert jobs[2].state is JobState.KILLED
    assert jobs[1].state is JobState.RUNNING
    assert jobs[0].state is JobState.RUNNING


def test_force_release_uses_idle_first():
    st, _ = make_st()
    st.grant(10, 0.0)
    j = Job(1, 0.0, 4, 100.0)
    st.submit(j, 0.0)
    assert st.idle == 6
    got = st.force_release(5, 0.0)
    assert got == 5
    assert j.state is JobState.RUNNING          # idle covered the reclaim
    assert st.alloc == 5 and st.idle == 1


def test_checkpoint_preempt_requeues_with_progress():
    cfg = SimConfig(preempt_mode="checkpoint", checkpoint_cost=10.0)
    st, _ = make_st(cfg)
    st.grant(4, 0.0)
    j = Job(1, 0.0, 4, 1000.0)
    st.submit(j, 0.0)
    st.force_release(4, now=500.0)
    assert j.state is JobState.QUEUED
    assert j.kills == 1
    # 500s elapsed - 10s checkpoint cost preserved
    assert j.checkpointed_work == pytest.approx(490.0)
    assert j.remaining() == pytest.approx(510.0)


def test_autoscaler_rule_up_and_down():
    # constant high load -> scale up by one per 20s window
    load = np.full(10, 1000.0)   # dt=20 -> one decision per sample
    d = demand_from_load(load, 20.0, capacity_per_instance=100.0)
    assert list(d[:5]) == [2, 3, 4, 5, 6]   # util>0.8 each window -> +1
    # low load -> scale down to floor 1
    load = np.full(10, 10.0)
    d2 = demand_from_load(load, 20.0, 100.0, n0=5)
    assert d2[-1] == 1 and d2[0] <= 5


def test_ws_server_tracks_unmet_demand():
    cfg = SimConfig()
    granted = {"n": 3}
    ws = WSServer(cfg, request=lambda n: min(n, granted["n"]),
                  release=lambda n: None)
    ws.set_demand(5, now=0.0)      # only 3 granted
    assert ws.alloc == 3
    ws.set_demand(5, now=10.0)     # 10s with shortfall 2
    assert ws.unmet_node_seconds == pytest.approx(20.0)


def test_ws_headroom_proxy_clamps_at_zero_without_latency_feed():
    """Regression (market PR): a replica shortfall made the surplus proxy
    predict NEGATIVE headroom, which inflated slo_elastic bids beyond the
    zero-headroom level — without any measured violation. The proxy must
    clamp at 0; a real observe_latency feed may still go negative."""
    from repro.core.policies import Tenant, unit_bid

    ws = WSServer(SimConfig(), request=lambda n: 0,   # nothing ever granted
                  release=lambda n: None,
                  slo=SLOConfig(latency_target_s=30.0))
    ws.set_demand(10, now=0.0)
    assert ws.alloc == 0                   # shortfall of 10 replicas
    assert ws.latency_headroom_s() == 0.0  # proxy clamped, not -300
    sig = ws.signals(0.0, name="ws")
    assert sig.latency_headroom_s == 0.0
    assert sig.queue_depth == 10           # shortfall still visible here
    # slo_elastic bid tops out at the zero-headroom level (2x), instead of
    # overshooting toward the violation cap on a mere prediction
    t = Tenant("ws", "latency", priority=0, bid_weight=2.0,
               bid_policy="slo_elastic")
    assert unit_bid(t, sig) == pytest.approx(4.0)
    # surplus still reports positive headroom (scaled by the target)
    ws.alloc = 15
    assert ws.latency_headroom_s() == pytest.approx(30.0 * 5 / 10)
    # a measured violation is real and stays negative
    ws.observe_latency(45.0)
    assert ws.latency_headroom_s() == pytest.approx(-15.0)
    assert unit_bid(t, ws.signals(0.0, name="ws")) == pytest.approx(5.0)
    # and without an SLO the proxy is the clamped surplus itself
    ws_no_slo = WSServer(SimConfig(), request=lambda n: 0,
                         release=lambda n: None)
    ws_no_slo.set_demand(4, now=0.0)
    assert ws_no_slo.latency_headroom_s() == 0.0
