"""Property-based tests (hypothesis) for the Phoenix Cloud invariants.

System invariants under arbitrary job sets and WS demand curves:
  * node conservation: free + st alloc + ws alloc == total, always;
  * WS priority: unmet demand only when demand exceeds total capacity;
  * ST never runs more nodes than allocated;
  * completed jobs have turnaround >= runtime;
  * every job ends in exactly one terminal/queue state.
"""
import math

import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.policies import POLICIES
from repro.core.simulator import ConsolidationSim
from repro.core.types import Job, JobState, SimConfig, paper_tenants

HOUR = 3600.0
HORIZON = 48 * HOUR


@st.composite
def job_sets(draw):
    n = draw(st.integers(1, 40))
    jobs = []
    for i in range(n):
        jobs.append(Job(
            job_id=i + 1,
            submit_time=draw(st.floats(0, HORIZON * 0.8)),
            size=draw(st.integers(1, 64)),
            runtime=draw(st.floats(60.0, 12 * HOUR)),
        ))
    return jobs


@st.composite
def demand_curves(draw):
    n = draw(st.integers(0, 25))
    times = sorted(draw(st.lists(st.floats(0, HORIZON), min_size=n,
                                 max_size=n)))
    return [(t, draw(st.integers(0, 80))) for t in times]


class AuditedSim(ConsolidationSim):
    """Checks conservation + allocation invariants after every event."""

    def run(self):
        # monkeypatch accounting hook to audit at every event boundary
        orig_account = self._account

        st = self._rt_by_name["st"].server
        ws = self._rt_by_name["ws"].server

        def audited(t):
            orig_account(t)
            self.svc.check()
            assert st.used <= st.alloc, (st.used, st.alloc)
            assert st.alloc == self.svc.tenants["st"].alloc
            assert ws.alloc == self.svc.tenants["ws"].alloc

        self._account = audited
        return super().run()


@given(jobs=job_sets(), demand=demand_curves(),
       total=st.integers(80, 256),
       mode=st.sampled_from(["kill", "checkpoint"]))
@settings(max_examples=60, deadline=None)
def test_invariants_hold(jobs, demand, total, mode):
    cfg = SimConfig(total_nodes=total, preempt_mode=mode)
    sim = AuditedSim(cfg, tenants=paper_tenants(jobs, demand),
                     horizon=HORIZON)
    res = sim.run()

    # WS priority: unmet only when demand > total
    max_demand = max((n for _, n in demand), default=0)
    if max_demand <= total:
        assert res.ws_unmet_node_seconds == 0.0

    for j in sim.jobs:
        if j.state is JobState.COMPLETED:
            assert j.turnaround >= j.remaining() - 1e-6
            assert j.end_time >= j.submit_time
        if mode == "checkpoint":
            assert j.state is not JobState.KILLED

    n_terminal = sum(j.state in (JobState.COMPLETED, JobState.KILLED,
                                 JobState.QUEUED, JobState.RUNNING)
                     for j in sim.jobs)
    assert n_terminal == len(sim.jobs)
    assert res.completed + res.killed <= res.submitted


@st.composite
def engine_tenant_sets(draw):
    n = draw(st.integers(2, 6))
    rows = []
    for i in range(n):
        kind = draw(st.sampled_from(["batch", "latency"]))
        floor = draw(st.integers(0, 6)) if kind == "latency" else 0
        rows.append((f"t{i}", kind, draw(st.integers(0, 5)),
                     draw(st.floats(0.0, 4.0)), floor))
    if not any(k == "latency" for _, k, _, _, _ in rows):
        name, _, prio, w, _ = rows[0]
        rows[0] = (name, "latency", prio, w, draw(st.integers(0, 6)))
    return rows


@given(total=st.integers(10, 300),
       policy=st.sampled_from(sorted(POLICIES)),
       rows=engine_tenant_sets(),
       ops=st.lists(
           st.tuples(st.sampled_from(["claim", "release", "demand",
                                      "armfail", "repair"]),
                     st.integers(0, 5),       # tenant index
                     st.integers(0, 120)),    # amount
           max_size=50))
@settings(max_examples=60, deadline=None)
def test_any_engine_conserves_and_respects_floors_under_faults(
        total, policy, rows, ops):
    """ANY PolicyEngine: node conservation holds and forced reclaim never
    takes a latency tenant below its floor — including when ``node_failed``
    fires MID-RECLAIM from inside a victim's force-release hook."""
    from repro.core.policies import Tenant
    from repro.core.provision import TenantProvisionService

    svc = TenantProvisionService(total, policy=policy)
    arm = {"fail": False, "repairs_due": 0}
    tenants = []

    def release_hook(name):
        def hook(n):
            rec = svc.tenants[name]
            if arm["fail"] and svc.total > 0:
                arm["fail"] = False
                svc.node_failed(name)       # a node dies mid-eviction
                arm["repairs_due"] += 1
            return min(n, rec.alloc)
        return hook

    for name, kind, prio, weight, floor in rows:
        tenants.append(svc.register(Tenant(
            name, kind, priority=prio, weight=weight, floor=floor,
            on_force_release=release_hook(name)
            if kind == "batch" else None)))

    for op, ti, n in ops:
        t = tenants[ti % len(tenants)]
        if op == "claim" and t.kind == "latency":
            # forced reclaim must not push any OTHER latency tenant below
            # min(its floor, its current alloc)
            before = {x.name: x.alloc for x in tenants
                      if x.kind == "latency" and x.name != t.name}
            got = svc.claim(t.name, n)
            assert 0 <= got <= n
            for x in tenants:
                if x.kind == "latency" and x.name != t.name:
                    assert x.alloc >= min(x.floor, before[x.name]), \
                        (x.name, x.alloc, x.floor, before[x.name])
        elif op == "release":
            svc.release(t.name, n)
        elif op == "demand" and t.kind == "batch":
            svc.set_demand(t.name, n)
        elif op == "armfail":
            arm["fail"] = True
        elif op == "repair" and arm["repairs_due"] > 0:
            svc.node_repaired()
            arm["repairs_due"] -= 1
        svc.check()
        assert sum(x.alloc for x in tenants) + svc.free == svc.total
        assert svc.free >= 0
        assert all(x.alloc >= 0 for x in tenants)


@given(total=st.integers(16, 300), req=st.lists(st.integers(1, 64),
                                                min_size=1, max_size=30))
@settings(max_examples=60, deadline=None)
def test_provision_service_conservation(total, req):
    from repro.core.provision import TenantProvisionService
    svc = TenantProvisionService(total, policy="paper")
    for spec in paper_tenants():
        svc.register_spec(spec)
    st_rec, ws_rec = svc.tenants["st"], svc.tenants["ws"]
    st_rec.on_force_release = lambda n: min(n, st_rec.alloc)
    svc.provision_idle()
    ws_alloc = 0
    for r in req:
        if ws_alloc > 0 and r % 3 == 0:
            give = min(ws_alloc, r)
            svc.release("ws", give)
            ws_alloc -= give
        else:
            got = svc.claim("ws", r)
            assert got <= r
            ws_alloc += got
        svc.check()
        assert ws_rec.alloc == ws_alloc
