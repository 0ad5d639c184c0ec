"""Multi-tenant provision service: N departments, strict priorities."""
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:       # container without hypothesis: property tests skip
    HAS_HYPOTHESIS = False

from repro.core.policies import Tenant
from repro.core.provision import TenantProvisionService


def make_service(total=100, policy="demand_capped"):
    """Strict priorities; ``"paper"`` dumps ALL leftover idle nodes on the
    highest-priority batch tenant, ``"demand_capped"`` stops grants at
    declared demand and leaves the remainder free."""
    svc = TenantProvisionService(total, policy=policy)
    freed = {"st1": 0, "st2": 0}

    def releaser(name):
        def f(n):
            freed[name] += n
            return n
        return f

    svc.register(Tenant("ws1", "latency", priority=0))
    svc.register(Tenant("ws2", "latency", priority=1))
    svc.register(Tenant("st1", "batch", priority=2,
                        on_force_release=releaser("st1")))
    svc.register(Tenant("st2", "batch", priority=3,
                        on_force_release=releaser("st2")))
    return svc, freed


def test_idle_flows_to_highest_priority_batch_greedy():
    svc, _ = make_service(policy="paper")
    svc.tenants["st1"].demand = 30
    svc.tenants["st2"].demand = 50
    svc.provision_idle()
    # st1 gets its demand, st2 gets its demand, leftover -> st1 (greedy)
    assert svc.tenants["st1"].alloc == 30 + 20
    assert svc.tenants["st2"].alloc == 50
    assert svc.free == 0


def test_idle_demand_capped_by_default():
    """Default mode: grants stop at declared demand, leftover stays free —
    a tenant with zero demand never receives nodes."""
    svc, _ = make_service()
    svc.tenants["st1"].demand = 30
    svc.tenants["st2"].demand = 50
    svc.provision_idle()
    assert svc.tenants["st1"].alloc == 30
    assert svc.tenants["st2"].alloc == 50
    assert svc.free == 20
    svc.check()


def test_zero_demand_tenant_gets_nothing_by_default():
    svc, _ = make_service()
    svc.provision_idle()
    assert svc.tenants["st1"].alloc == 0
    assert svc.tenants["st2"].alloc == 0
    assert svc.free == 100
    svc.check()


def test_two_tenant_special_case_matches_paper():
    """With one WS + one ST and the paper policy this reduces to the
    paper's three rules."""
    svc = TenantProvisionService(10, policy="paper")
    svc.register(Tenant("ws", "latency", priority=0))
    svc.register(Tenant("st", "batch", priority=1,
                        on_force_release=lambda n: n))
    svc.provision_idle()
    assert svc.tenants["st"].alloc == 10          # rule 2: all idle to ST
    got = svc.claim("ws", 4)                      # rule 3: forced reclaim
    assert got == 4
    assert svc.tenants["ws"].alloc == 4 and svc.tenants["st"].alloc == 6
    svc.release("ws", 2)                          # WS releases immediately
    assert svc.tenants["st"].alloc == 8           # ... and idle goes to ST


def test_reclaim_order_reverse_priority():
    svc, freed = make_service()
    svc.tenants["st1"].demand = 60
    svc.tenants["st2"].demand = 40
    svc.provision_idle()
    # claim more than st2 (lowest priority) holds: st2 drained before st1
    got = svc.claim("ws1", 50)
    assert got == 50
    assert freed["st2"] == 40
    assert freed["st1"] == 10
    assert svc.tenants["st2"].alloc == 0


def test_reclaim_drains_all_batch_before_latency_tenants():
    """Claim ordering: batch tenants (reverse priority) are fully drained
    before any lower-priority latency tenant is touched."""
    svc, freed = make_service()
    svc.set_demand("st1", 20)
    svc.set_demand("st2", 20)
    svc.claim("ws2", 60)               # ws2 takes the free pool
    assert svc.free == 0
    # ws1 needs 50: free(0) -> st2(20) -> st1(20) -> only then ws2(10)
    got = svc.claim("ws1", 50)
    assert got == 50
    assert freed["st2"] == 20 and freed["st1"] == 20
    assert svc.tenants["st1"].alloc == 0 and svc.tenants["st2"].alloc == 0
    assert svc.tenants["ws2"].alloc == 50          # lost exactly the rest
    assert svc.tenants["ws1"].alloc == 50


def test_reclaim_spares_latency_when_batch_suffices():
    svc, freed = make_service()
    svc.set_demand("st1", 30)
    svc.claim("ws2", 40)
    got = svc.claim("ws1", 55)          # free 30 + st1's 30 > 55 - no ws2 hit
    assert got == 55
    assert freed["st1"] == 25
    assert svc.tenants["ws2"].alloc == 40          # untouched
    assert svc.tenants["st1"].alloc == 5


def test_latency_tenants_preempt_lower_priority_latency():
    svc, _ = make_service()
    svc.claim("ws2", 100)          # ws2 grabs everything
    got = svc.claim("ws1", 30)     # higher-priority ws1 preempts ws2
    assert got == 30
    assert svc.tenants["ws1"].alloc == 30
    assert svc.tenants["ws2"].alloc == 70


def test_lower_priority_latency_cannot_preempt_higher():
    svc, _ = make_service()
    svc.claim("ws1", 100)
    got = svc.claim("ws2", 10)     # nothing reclaimable below ws2
    assert got == 0
    assert svc.tenants["ws1"].alloc == 100


if not HAS_HYPOTHESIS:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_conservation_under_arbitrary_ops():
        pass
else:
    @given(total=st.integers(10, 200),
           policy=st.sampled_from(["paper", "demand_capped"]),
           ops=st.lists(st.tuples(st.sampled_from(["claim1", "claim2",
                                                   "rel1", "rel2",
                                                   "demand1", "demand2"]),
                                  st.integers(0, 80)), max_size=40))
    @settings(max_examples=80, deadline=None)
    def test_conservation_under_arbitrary_ops(total, policy, ops):
        svc, _ = make_service(total, policy=policy)
        for op, n in ops:
            if op == "claim1":
                svc.claim("ws1", n)
            elif op == "claim2":
                svc.claim("ws2", n)
            elif op == "rel1":
                svc.release("ws1", n)
            elif op == "rel2":
                svc.release("ws2", n)
            elif op == "demand1":
                svc.set_demand("st1", n)
            else:
                svc.set_demand("st2", n)
            svc.check()
            assert svc.free >= 0
