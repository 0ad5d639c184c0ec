"""Equivalence of the queue-simulator implementations.

The vectorized fast paths (no-wait check + constant-capacity
Kiefer–Wolfowitz recurrence), the event-merged piecewise sweep, and the
original per-request reference loop must produce *identical*
``QueueMetrics`` — bit-for-bit, since all exact paths do the same float64
arithmetic — across constant and stepped capacity traces, including the
unserved / horizon-cutoff edge cases. The jax batched core runs in float32
and is held to golden tolerance instead.
"""
import numpy as np
import pytest

from repro.core.types import SLOConfig
from repro.serving.batching import ServiceTimeModel
from repro.workloads.arrivals import make_trace
from repro.workloads.queueing import (SIM_COUNTERS, QueueJob, capacity_steps,
                                      counters_delta, plan_queue_buckets,
                                      simulate_queue, simulate_queue_batch,
                                      simulate_queue_many,
                                      simulate_queue_reference,
                                      snapshot_counters)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:       # container without hypothesis: property tests skip
    HAVE_HYPOTHESIS = False

MODEL = ServiceTimeModel()
SLO = SLOConfig(latency_target_s=30.0)
KINDS = ("poisson", "mmpp", "diurnal", "flash_crowd")


def random_capacity(rng, horizon, max_nodes=10, max_steps=12):
    """Random piecewise capacity, deliberately including zero levels."""
    ev = [(0.0, int(rng.integers(0, max_nodes)))]
    for _ in range(int(rng.integers(0, max_steps))):
        ev.append((float(rng.uniform(0.0, horizon)),
                   int(rng.integers(0, max_nodes))))
    return ev


def assert_same(a, b, ctx=""):
    assert a == b, f"{ctx}\n  {a}\n  {b}"


def assert_golden(m, ref, ctx="", rtol=3e-4, atol=2e-3):
    """float32 batched metrics vs a float64 exact oracle.

    float32 drift can flip borderline served/unserved decisions right at
    capacity-window and horizon edges; tolerate a small flip count, and
    when a flip did occur the percentile stats straddle different request
    sets, so only the count is compared."""
    assert m.n_requests == ref.n_requests, ctx
    flip_tol = max(2, int(0.002 * max(ref.n_requests, 1)))
    assert abs(m.unserved - ref.unserved) <= flip_tol, \
        (ctx, m.unserved, ref.unserved)
    if m.unserved != ref.unserved:
        return
    for f in ("p50_s", "p95_s", "p99_s", "mean_s", "max_s", "mean_wait_s",
              "violation_rate"):
        a, b = getattr(m, f), getattr(ref, f)
        ok = (np.isinf(a) and np.isinf(b)) or np.isclose(a, b, rtol=rtol,
                                                         atol=atol)
        assert ok, (ctx, f, a, b)


# ----------------------------------------------------- randomized sweeps


@pytest.mark.parametrize("seed", range(6))
def test_all_impls_agree_on_random_piecewise(seed):
    rng = np.random.default_rng(seed)
    kind = KINDS[seed % len(KINDS)]
    horizon = 3600.0
    tr = make_trace(kind, float(rng.uniform(0.3, 4.0)), horizon, seed)
    for _ in range(4):
        ev = random_capacity(rng, horizon)
        for hz in (horizon, 0.5 * horizon, None):
            ref = simulate_queue_reference(tr, ev, MODEL, SLO, horizon=hz)
            auto = simulate_queue(tr, ev, MODEL, SLO, horizon=hz)
            evn = simulate_queue(tr, ev, MODEL, SLO, horizon=hz,
                                 impl="event")
            assert_same(ref, auto, f"auto {kind} {ev[:3]} hz={hz}")
            assert_same(ref, evn, f"event {kind} {ev[:3]} hz={hz}")


@pytest.mark.parametrize("seed", range(4))
def test_all_impls_agree_on_constant_capacity(seed):
    rng = np.random.default_rng(100 + seed)
    tr = make_trace(KINDS[seed % len(KINDS)],
                    float(rng.uniform(0.5, 3.0)), 3600.0, seed)
    for nodes in (0, 1, int(rng.integers(2, 8)), 500):
        ev = [(0.0, nodes)]
        ref = simulate_queue_reference(tr, ev, MODEL, SLO, horizon=3600.0)
        auto = simulate_queue(tr, ev, MODEL, SLO, horizon=3600.0)
        assert_same(ref, auto, f"constant k={nodes}")
        if nodes > 0:
            fast = simulate_queue(tr, ev, MODEL, SLO, horizon=3600.0,
                                  impl="fast")
            assert_same(ref, fast, f"fast k={nodes}")


# ----------------------------------------------------------- edge cases


def test_unserved_horizon_cutoff_agrees():
    tr = make_trace("poisson", 1.0, 600.0, seed=0)
    # starvation window then rescue, cut at a horizon inside the backlog
    ev = [(0.0, 0), (300.0, 1), (450.0, 0), (500.0, 2)]
    ref = simulate_queue_reference(tr, ev, MODEL, SLO, horizon=550.0)
    auto = simulate_queue(tr, ev, MODEL, SLO, horizon=550.0)
    assert_same(ref, auto)
    assert ref.unserved > 0


def test_zero_capacity_all_unserved_agrees():
    tr = make_trace("poisson", 1.0, 600.0, seed=0)
    for impl in ("auto", "event", "reference"):
        m = simulate_queue(tr, [(0.0, 0)], MODEL, SLO, horizon=600.0,
                           impl=impl)
        assert m.unserved == len(tr)
        assert m.violation_rate == 1.0 and not m.slo_met


def test_empty_trace():
    tr = make_trace("poisson", 1.0, 600.0, seed=0)
    empty = type(tr)(np.empty(0), np.empty(0, np.int64),
                     np.empty(0, np.int64))
    for impl in ("auto", "event", "reference"):
        m = simulate_queue(empty, [(0.0, 4)], MODEL, SLO, impl=impl)
        assert m.n_requests == 0 and m.slo_met


def test_fast_impl_rejects_contended_piecewise():
    tr = make_trace("poisson", 2.0, 3600.0, seed=0)
    with pytest.raises(ValueError):
        simulate_queue(tr, [(0.0, 1), (600.0, 2)], MODEL, SLO,
                       horizon=3600.0, impl="fast")
    with pytest.raises(ValueError):
        simulate_queue(tr, [(0.0, 4)], MODEL, SLO, impl="nope")


def test_no_wait_path_used_and_counted():
    tr = make_trace("poisson", 0.5, 1800.0, seed=0)
    before = snapshot_counters()
    m = simulate_queue(tr, [(0.0, 1000)], MODEL, SLO, horizon=1800.0)
    d = counters_delta(before)
    assert d["no_wait"] == 1 and d["requests"] == len(tr)
    assert d["seconds"] > 0
    assert m.mean_wait_s == 0.0
    ref = simulate_queue_reference(tr, [(0.0, 1000)], MODEL, SLO,
                                   horizon=1800.0)
    assert_same(ref, m)


def test_capacity_steps_unchanged_semantics():
    t, k = capacity_steps([(5.0, 2), (0.0, 1), (5.0, 3)], slots_per_node=4)
    assert list(t) == [0.0, 5.0]
    assert list(k) == [4, 12]


# ------------------------------------------------------------ jax batched


def test_simulate_queue_many_matches_exact_paths():
    traces = [make_trace(k, 1.5, 1800.0, s)
              for s, k in enumerate(("poisson", "mmpp", "flash_crowd"))]
    caps = [[(0.0, 2)], [(0.0, 4)], [(0.0, 1), (600.0, 3)]]  # mixed const/pw
    many = simulate_queue_many(traces, caps, MODEL, SLO, horizon=1800.0)
    assert len(many) == len(traces)
    for tr, ev, m in zip(traces, caps, many):
        ex = simulate_queue(tr, ev, MODEL, SLO, horizon=1800.0)
        assert m.n_requests == ex.n_requests
        assert m.unserved == ex.unserved
        for f in ("p50_s", "p95_s", "p99_s", "mean_s", "mean_wait_s",
                  "violation_rate"):
            a, b = getattr(m, f), getattr(ex, f)
            assert (np.isinf(a) and np.isinf(b)) or \
                np.isclose(a, b, rtol=2e-4, atol=1e-3), (f, a, b)


def test_simulate_queue_many_numpy_backend_exact():
    traces = [make_trace("poisson", 1.0, 900.0, s) for s in range(2)]
    caps = [[(0.0, 2)], [(0.0, 3)]]
    many = simulate_queue_many(traces, caps, MODEL, SLO, horizon=900.0,
                               backend="numpy")
    for tr, ev, m in zip(traces, caps, many):
        assert_same(simulate_queue_reference(tr, ev, MODEL, SLO,
                                             horizon=900.0), m)


# ------------------------------------------- piecewise jax batched path


def _pw_jobs(seed, n_cells=8, horizon=1800.0, max_steps=10):
    rng = np.random.default_rng(seed)
    jobs = []
    for i in range(n_cells):
        tr = make_trace(KINDS[i % len(KINDS)],
                        float(rng.uniform(0.4, 3.0)), horizon, seed + i)
        ev = random_capacity(rng, horizon, max_steps=max_steps)
        if len(ev) == 1:               # force a genuinely piecewise cell
            ev.append((horizon / 2, int(rng.integers(0, 10))))
        jobs.append(QueueJob(tr, ev, MODEL, SLO, horizon=horizon))
    return jobs


@pytest.mark.parametrize("seed", range(4))
def test_batched_piecewise_matches_reference(seed):
    jobs = _pw_jobs(seed)
    tags: list = []
    many = simulate_queue_batch(jobs, stats_out=tags)
    assert tags.count("jax_batched") in (0, len(jobs))  # all or no-JAX
    for job, m in zip(jobs, many):
        ref = simulate_queue_reference(job.trace, job.capacity_events,
                                       job.model, job.slo,
                                       horizon=job.horizon)
        assert_golden(m, ref, f"seed={seed} ev={job.capacity_events[:3]}")


def test_batched_piecewise_edge_cases():
    """Zero-capacity windows, capacity drop mid-queue, horizon cutoff in
    the backlog — the drain semantics of the blocked-search oracle."""
    tr = make_trace("poisson", 1.0, 600.0, seed=0)
    cases = [
        ([(0.0, 0)], 600.0),                               # never serves
        ([(0.0, 0), (300.0, 1), (450.0, 0), (500.0, 2)], 550.0),
        ([(0.0, 5), (100.0, 1)], 600.0),                   # drop mid-queue
        ([(0.0, 2), (200.0, 0), (400.0, 2)], 600.0),       # outage window
        ([(0.0, 1), (590.0, 8)], 595.0),                   # cutoff at edge
    ]
    jobs = [QueueJob(tr, ev, MODEL, SLO, horizon=hz) for ev, hz in cases]
    many = simulate_queue_batch(jobs)
    for job, m in zip(jobs, many):
        ref = simulate_queue_reference(tr, job.capacity_events, MODEL, SLO,
                                       horizon=job.horizon)
        assert_golden(m, ref, f"ev={job.capacity_events}")
    assert many[0].unserved == len(tr)


def test_batched_composition_independent():
    """A cell's batched metrics must not depend on what it was co-batched
    with: bucket shapes are pure per-cell functions (n_pad) or value
    invariant (e/k padded to batch max), so solo == co-batched exactly."""
    jobs = _pw_jobs(42, n_cells=6)
    solo = [simulate_queue_batch([j])[0] for j in jobs]
    grouped = simulate_queue_batch(jobs)
    for a, b in zip(solo, grouped):
        assert a == b      # bitwise, not golden-tolerance


def test_batched_mixed_const_and_piecewise_buckets():
    horizon = 1200.0
    tr1 = make_trace("poisson", 1.5, horizon, seed=1)
    tr2 = make_trace("mmpp", 1.5, horizon, seed=2)
    jobs = [QueueJob(tr1, [(0.0, 2)], MODEL, SLO, horizon),
            QueueJob(tr2, [(0.0, 1), (600.0, 3)], MODEL, SLO, horizon),
            QueueJob(tr1, [(0.0, 4)], MODEL, SLO, horizon),
            QueueJob(tr2, [(0.0, 3), (300.0, 0), (700.0, 2)], MODEL, SLO,
                     horizon)]
    kinds = {k[0] for k in plan_queue_buckets(jobs)}
    many = simulate_queue_batch(jobs)
    assert kinds <= {"const", "pw"} and len(kinds) in (1, 2)
    for job, m in zip(jobs, many):
        ref = simulate_queue_reference(job.trace, job.capacity_events,
                                       MODEL, SLO, horizon=horizon)
        assert_golden(m, ref, f"ev={job.capacity_events}")


def test_batched_counter_attribution():
    import jax
    from repro.workloads.queueing import SERVED_ON
    jobs = _pw_jobs(7, n_cells=3)
    before = snapshot_counters()
    served0 = dict(SERVED_ON)
    tags: list = []
    simulate_queue_batch(jobs, stats_out=tags)
    d = counters_delta(before)
    assert d["calls"] == 3 and d["requests"] == sum(len(j.trace)
                                                    for j in jobs)
    assert tags == ["jax_batched"] * 3 and d["jax_batched"] == 3
    platform = jax.default_backend()
    assert SERVED_ON[platform] - served0.get(platform, 0) == 3


def test_batched_path_does_not_swallow_jax_errors(monkeypatch):
    """No silent numpy fallback: a failure to reach JAX surfaces."""
    from repro.workloads import queueing

    def broken():
        raise ImportError("jax is part of the installation")

    monkeypatch.setattr(queueing, "_jax_modules", broken)
    with pytest.raises(ImportError):
        simulate_queue_batch(_pw_jobs(3, n_cells=2))


# ------------------------------------------------- bucket plan regression


def test_bucket_padding_stays_proportional():
    """Regression for the old global-pad behaviour: one huge trace used to
    inflate every cell to its padded length. With shape buckets the total
    padded element count must stay within a constant factor of the sum of
    the actual cell sizes — regardless of size skew in the batch."""
    rng = np.random.default_rng(3)
    horizon = 1800.0
    jobs = []
    sizes = [60, 120, 450, 900, 1800, 3600, 7000, 14000]
    for i, n_target in enumerate(sizes):
        rate = n_target / horizon
        tr = make_trace("poisson", rate, horizon, seed=i)
        ev = random_capacity(rng, horizon) if i % 2 else [(0.0, 4)]
        jobs.append(QueueJob(tr, ev, MODEL, SLO, horizon))
    buckets = plan_queue_buckets(jobs)
    total_padded = sum(len(rows) * key[1] for key, rows in buckets.items())
    total_actual = sum(len(j.trace) for j in jobs)
    # floor=256 means tiny cells pad hard; everything else is <2x. Under
    # the old single global pad this ratio was ~len(jobs) for skewed sets.
    floor_slack = sum(max(256 - len(j.trace), 0) for j in jobs)
    assert total_padded <= 2 * total_actual + floor_slack
    # and every job with a non-empty trace is planned exactly once
    planned = sorted(i for rows in buckets.values() for i in rows)
    assert planned == list(range(len(jobs)))


def test_bucket_key_is_per_cell_pure():
    """n_pad must depend only on the cell itself (fold reduction-tree
    shape), never on batch company — shard merges rely on it."""
    jobs = _pw_jobs(11, n_cells=5)
    solo_keys = {}
    for i, j in enumerate(jobs):
        (key, rows), = plan_queue_buckets([j]).items()
        solo_keys[i] = key
    grouped = plan_queue_buckets(jobs)
    for key, rows in grouped.items():
        for i in rows:
            assert solo_keys[i] == key


# ------------------------------------------------- hypothesis (optional)


if HAVE_HYPOTHESIS:

    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 10_000),
           rate=st.floats(0.1, 5.0),
           nodes=st.integers(0, 8),
           steps=st.integers(0, 8))
    def test_property_impls_identical(seed, rate, nodes, steps):
        rng = np.random.default_rng(seed)
        tr = make_trace(KINDS[seed % len(KINDS)], rate, 1200.0, seed)
        ev = [(0.0, nodes)]
        for _ in range(steps):
            ev.append((float(rng.uniform(0, 1200.0)),
                       int(rng.integers(0, 8))))
        ref = simulate_queue_reference(tr, ev, MODEL, SLO, horizon=1200.0)
        auto = simulate_queue(tr, ev, MODEL, SLO, horizon=1200.0)
        assert ref == auto

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 10_000),
           rate=st.floats(0.2, 3.0),
           nodes=st.integers(0, 8),
           steps=st.integers(1, 10),
           hz_frac=st.floats(0.3, 1.0))
    def test_property_batched_piecewise_golden(seed, rate, nodes, steps,
                                               hz_frac):
        """The jax piecewise batched core vs the reference oracle under
        random capacity schedules (incl. zero windows) and horizon cuts."""
        rng = np.random.default_rng(seed)
        tr = make_trace(KINDS[seed % len(KINDS)], rate, 1200.0, seed)
        ev = [(0.0, nodes)]
        for _ in range(steps):
            ev.append((float(rng.uniform(0, 1200.0)),
                       int(rng.integers(0, 8))))
        hz = 1200.0 * hz_frac
        m = simulate_queue_batch([QueueJob(tr, ev, MODEL, SLO, hz)])[0]
        ref = simulate_queue_reference(tr, ev, MODEL, SLO, horizon=hz)
        assert_golden(m, ref, f"ev={ev[:4]} hz={hz:.0f}")
else:

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_property_impls_identical():
        pass

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_property_batched_piecewise_golden():
        pass
