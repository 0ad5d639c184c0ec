"""M/G/k-style replica queue with continuous-batching service times.

Each WS node runs one serving replica with ``ServiceTimeModel.max_batch``
concurrent slots (the same knob as ``ContinuousBatcher``); the cluster is a
FIFO queue over ``k(t) = nodes(t) * slots_per_replica`` slots. Capacity is
piecewise-constant in time, so the same simulator measures both the
autoscaler's *planned* latency and the latency *realized* under whatever the
Resource Provision Service actually granted (they differ exactly when WS
demand went unmet — the tail the paper's node-demand timeseries can't see).

Capacity drops do not kill in-flight requests (nodes drain, matching the WS
CMS's release-idle-nodes policy); they only gate new starts.

Implementations (all agree bit-for-bit on float64, enforced by
tests/test_queueing_equivalence.py):

  * ``no_wait``   — vectorized numpy O(N log N): when no request ever
                    queues (checked exactly), latency == service time.
  * ``constant``  — constant capacity k: FIFO M/G/k reduces to the
                    Kiefer–Wolfowitz k-slot rolling-finish recurrence
                    (replace the earliest-free slot), O(N log k).
  * ``event``     — piecewise capacity: two-pointer event-merged sweep,
                    O((N + E) log k) with an O(E) next-capacity-rise
                    table instead of a searchsorted per retry.
  * ``reference`` — the original per-request loop with a binary-search
                    capacity lookup inside a retry loop; kept as the
                    golden oracle and the benchmark baseline.

``simulate_queue_batch`` (and its ``simulate_queue_many`` wrapper) batches
heterogeneous cells through shape-bucketed ``jit(vmap(lax.scan))`` device
programs — a Kiefer–Wolfowitz core for constant capacity and a k(t)-aware
sorted-slot core for piecewise capacity — with the metric fold fused on
device (float32 — golden-tolerance, not bit-identical).
"""
from __future__ import annotations

import dataclasses
import heapq
import time
from collections import OrderedDict
from math import inf as _INF
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.types import SLOConfig
from repro.serving.batching import ServiceTimeModel
from repro.workloads.arrivals import RequestTrace

# running totals across simulate_queue calls: the campaign snapshots these
# around each cell to report queue-sim requests/sec in its artifact (one
# dict per process; cells return deltas, so process pools stay correct)
SIM_COUNTERS: Dict[str, float] = {
    "calls": 0, "requests": 0, "seconds": 0.0,
    "no_wait": 0, "constant": 0, "event": 0, "reference": 0,
    "jax_batched": 0,
}


# batched queue jobs served per device platform ("tpu", "cpu", ...), the
# record of where the device cores actually ran
SERVED_ON: Dict[str, int] = {}


def snapshot_counters() -> Dict[str, float]:
    return dict(SIM_COUNTERS)


def counters_delta(before: Dict[str, float]) -> Dict[str, float]:
    return {k: SIM_COUNTERS[k] - before.get(k, 0) for k in SIM_COUNTERS}


@dataclasses.dataclass
class QueueMetrics:
    n_requests: int
    n_served: int
    p50_s: float
    p95_s: float
    p99_s: float
    mean_s: float
    max_s: float
    mean_wait_s: float
    violation_rate: float          # frac(latency > slo.latency_target_s)
    slo_met: bool                  # violation_rate <= slo.max_violation_rate
    unserved: int                  # never started before horizon

    def as_dict(self) -> Dict[str, float]:
        return dataclasses.asdict(self)


def capacity_steps(events: Sequence[Tuple[float, int]],
                   slots_per_node: int = 1
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Normalize (time, nodes) change events into step arrays (times, slots).

    Events need not be sorted or deduplicated; the last level at a given
    time wins. Capacity before the first event is 0.
    """
    if not events:
        return np.array([0.0]), np.array([0], dtype=np.int64)
    # stable sort on time only: among same-time events the last logged wins
    ev = sorted(events, key=lambda e: e[0])
    times, levels = [0.0], [0]
    for t, n in ev:
        lvl = int(n) * slots_per_node
        if t == times[-1]:
            levels[-1] = lvl
        else:
            times.append(float(t))
            levels.append(lvl)
    return np.asarray(times), np.asarray(levels, dtype=np.int64)


# ----------------------------------------------------------- metric fold


def _metrics(n: int, lat: np.ndarray, wait: np.ndarray, unserved: int,
             slo: SLOConfig) -> QueueMetrics:
    """Fold per-request latency/wait arrays into QueueMetrics (shared by
    every implementation, so they can only disagree on the arrays)."""
    served = np.isfinite(lat)
    n_served = int(served.sum())
    viol = float(np.mean(~served | (lat > slo.latency_target_s)))
    if n_served == 0:
        return QueueMetrics(n, 0, np.inf, np.inf, np.inf, np.inf, np.inf,
                            np.inf, 1.0, False, unserved)
    sl = lat[served]
    p50, p95, p99 = np.percentile(sl, [50.0, 95.0, 99.0])
    return QueueMetrics(
        n_requests=n,
        n_served=n_served,
        p50_s=float(p50),
        p95_s=float(p95),
        p99_s=float(p99),
        mean_s=float(sl.mean()),
        max_s=float(sl.max()),
        mean_wait_s=float(wait[served].mean()),
        violation_rate=viol,
        slo_met=viol <= slo.max_violation_rate,
        unserved=unserved,
    )


# ------------------------------------------------------- implementations


def _try_no_wait(t: np.ndarray, svc: np.ndarray, cap_t: np.ndarray,
                 cap_k: np.ndarray, horizon: float
                 ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Fully vectorized fast path: if no request would ever queue, latency
    is exactly the service time. Returns None when any request waits.

    With FIFO starts at the arrival instants, request i finds
    ``#{j < i : t_j + svc_j > t_i}`` slots busy; since arrivals are sorted
    and service times positive, that count is a single global searchsorted
    over the optimistic finish times. The check is exact, so the arrays
    returned are bit-identical to what the reference loop would produce.
    """
    n = len(t)
    if n == 0 or float(svc.min()) <= 0.0 or float(t[-1]) >= horizon:
        return None
    fin = t + svc
    # cheap prefix probe: queueing in the first block rejects congested
    # cells without paying the full-array sort
    probe = 2048
    if n > probe:
        tp = t[:probe]
        kp = cap_k[np.searchsorted(cap_t, tp, side="right") - 1]
        infl_p = (np.arange(probe)
                  - np.searchsorted(np.sort(fin[:probe]), tp, side="right"))
        if not np.all(infl_p < kp):
            return None
    k_at = cap_k[np.searchsorted(cap_t, t, side="right") - 1]
    inflight = np.arange(n) - np.searchsorted(np.sort(fin), t, side="right")
    if not np.all(inflight < k_at):
        return None
    return fin - t, np.zeros(n)


def _simulate_constant(t: np.ndarray, svc: np.ndarray, k: int,
                       horizon: float
                       ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Constant-capacity FIFO M/G/k: Kiefer–Wolfowitz rolling-finish
    recurrence over a k-slot heap of slot-free times, O(N log k).

    A request starts at max(arrival, earliest slot-free time) and replaces
    that slot's finish — no capacity lookups, no retry loop. Bit-identical
    to the reference loop (same max/add float64 arithmetic).
    """
    n = len(t)
    lat = [_INF] * n
    wait = [_INF] * n
    if k <= 0:
        return np.asarray(lat), np.asarray(wait), n
    sl = svc.tolist()
    heapreplace = heapq.heapreplace
    heappush = heapq.heappush
    busy: List[float] = []          # slot free times, at most k entries
    unserved = 0
    for i, t0 in enumerate(t.tolist()):
        if len(busy) < k:
            if t0 >= horizon:
                unserved += 1
                continue
            fin = t0 + sl[i]
            heappush(busy, fin)
            lat[i] = fin - t0
            wait[i] = 0.0
            continue
        m = busy[0]
        start = t0 if t0 > m else m
        if start >= horizon:
            unserved += 1
            continue
        fin = start + sl[i]
        heapreplace(busy, fin)
        wait[i] = start - t0
        lat[i] = fin - t0
    return np.asarray(lat), np.asarray(wait), unserved


def _next_rise(cap_k: Sequence[int]) -> List[int]:
    """next_rise[j] = smallest j' > j with cap_k[j'] > cap_k[j], else nc.

    Monotonic-stack precompute so the event-merged sweep finds "when does
    capacity next exceed the current level" in O(1) instead of scanning."""
    nc = len(cap_k)
    out = [nc] * nc
    stack: List[int] = []
    for j in range(nc):
        kj = cap_k[j]
        while stack and cap_k[stack[-1]] < kj:
            out[stack.pop()] = j
        stack.append(j)
    return out


def _simulate_event(t: np.ndarray, svc: np.ndarray, cap_t: np.ndarray,
                    cap_k: np.ndarray, horizon: float
                    ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Piecewise-capacity FIFO sweep: two pointers (requests, capacity
    events) merged in time, O((N + E) log k).

    The capacity interval of every *arrival* is precomputed in one
    vectorized searchsorted; the scalar pointer only walks events for the
    requests whose start was pushed past their arrival by the FIFO queue.
    It advances monotonically with the committed start time (which is
    nondecreasing across *served* requests); a request that turns out
    unserved searches with a local copy so future capacity never leaks
    back to earlier arrivals. Blocked requests jump straight to
    min(earliest finish, next capacity rise) via the ``_next_rise`` table
    instead of rescanning events per retry. Bit-identical to the
    reference loop.
    """
    n = len(t)
    sl = svc.tolist()
    ct = cap_t.tolist()
    ck = cap_k.tolist()
    nc = len(ct)
    ngr = _next_rise(ck)
    heappush = heapq.heappush
    heappop = heapq.heappop
    lat = [_INF] * n
    wait = [_INF] * n
    ci_of_t = (np.searchsorted(cap_t, t, side="right") - 1).tolist()
    busy: List[float] = []          # completion-time heap of in-flight slots
    blen = 0                        # len(busy), tracked to skip len() calls
    unserved = 0
    prev_start = 0.0                # FIFO discipline: a request never starts
    ci_done = 0                     # capacity interval at prev_start
    for i, t0 in enumerate(t.tolist()):
        if t0 >= prev_start:        # common case: arrival interval known
            start = t0
            ci = ci_of_t[i]
        else:
            start = prev_start
            ci = ci_done
            while ci + 1 < nc and ct[ci + 1] <= start:
                ci += 1
        while True:
            k = ck[ci]
            while blen and busy[0] <= start:
                heappop(busy)
                blen -= 1
            if blen < k:
                break
            # blocked: wait for a slot to free or capacity to rise
            cand = busy[0] if blen else _INF
            jn = ngr[ci]
            if jn < nc and ct[jn] < cand:
                cand = ct[jn]
            if cand == _INF:
                start = _INF
                break
            if cand > start:
                start = cand
            if start >= horizon:
                start = _INF
                break
            while ci + 1 < nc and ct[ci + 1] <= start:
                ci += 1
        if start >= horizon:            # also catches start == inf
            unserved += 1
            continue
        prev_start = start
        ci_done = ci
        fin = start + sl[i]
        heappush(busy, fin)
        blen += 1
        wait[i] = start - t0
        lat[i] = fin - t0
    return np.asarray(lat), np.asarray(wait), unserved


def _simulate_reference(t: np.ndarray, svc: np.ndarray, cap_t: np.ndarray,
                        cap_k: np.ndarray, horizon: float
                        ) -> Tuple[np.ndarray, np.ndarray, int]:
    """The original per-request loop (searchsorted capacity lookup inside a
    retry loop). Kept verbatim as the golden oracle and bench baseline."""
    n = len(t)
    busy: List[float] = []          # completion-time heap of in-flight slots
    lat = np.empty(n)
    wait = np.empty(n)
    unserved = 0
    nc = len(cap_t)
    prev_start = 0.0                # FIFO discipline: a request never starts
    #                                 before the one queued ahead of it

    for i in range(n):
        t0 = float(t[i])
        start = max(t0, prev_start)
        while True:
            # capacity level AT `start` (looked up per request — a global
            # monotone pointer would apply a later capacity step to this
            # request whenever an earlier one blocked past it)
            ci = int(np.searchsorted(cap_t, start, side="right")) - 1
            k = int(cap_k[ci])
            while busy and busy[0] <= start:
                heapq.heappop(busy)
            if len(busy) < k:
                break
            # blocked: wait for a slot to free or capacity to rise
            nxt = []
            if busy:
                nxt.append(busy[0])
            j = ci + 1
            while j < nc:
                if cap_k[j] > k:
                    nxt.append(float(cap_t[j]))
                    break
                j += 1
            if not nxt:
                start = np.inf
                break
            start = max(start, min(nxt))
            if start >= horizon:
                start = np.inf
                break
        if not np.isfinite(start) or start >= horizon:
            unserved += 1
            lat[i] = np.inf
            wait[i] = np.inf
            continue
        prev_start = start
        fin = start + float(svc[i])
        heapq.heappush(busy, fin)
        wait[i] = start - t0
        lat[i] = fin - t0
    return lat, wait, unserved


IMPLS = ("auto", "fast", "event", "reference")


def simulate_queue(trace: RequestTrace,
                   capacity_events: Sequence[Tuple[float, int]],
                   model: ServiceTimeModel,
                   slo: SLOConfig,
                   horizon: Optional[float] = None,
                   impl: str = "auto") -> QueueMetrics:
    """FIFO M/G/k(t) simulation; returns latency + SLO metrics.

    capacity_events: (time, n_nodes) change events (each node contributes
    ``model.slots_per_replica`` slots). Requests that cannot start before
    `horizon` (capacity starvation) count as unserved AND as violations —
    an unserved request is the worst possible latency.

    impl: ``auto`` picks the fastest exact path (vectorized no-wait ->
    constant-capacity recurrence -> event-merged sweep); ``fast`` forces
    the vectorized family (raises on piecewise capacity with queueing);
    ``event`` and ``reference`` force those loops. All paths produce
    bit-identical float64 metrics.
    """
    if impl not in IMPLS:
        raise ValueError(f"unknown impl {impl!r}; have {IMPLS}")
    n = len(trace)
    if horizon is None:
        horizon = float(trace.t[-1]) + 1e9 if n else 0.0
    if n == 0:
        return QueueMetrics(0, 0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0,
                            True, 0)

    t0_wall = time.perf_counter()
    svc = model.service_times(trace.prompt_tokens, trace.decode_tokens)
    cap_t, cap_k = capacity_steps(capacity_events, model.slots_per_replica)
    t = np.asarray(trace.t, dtype=np.float64)
    horizon = float(horizon)
    constant = bool(np.all(cap_k == cap_k[0]))

    used = impl
    if impl == "reference":
        lat, wait, unserved = _simulate_reference(t, svc, cap_t, cap_k,
                                                  horizon)
    elif impl == "event":
        lat, wait, unserved = _simulate_event(t, svc, cap_t, cap_k, horizon)
    else:
        nw = _try_no_wait(t, svc, cap_t, cap_k, horizon)
        if nw is not None:
            lat, wait = nw
            unserved = 0
            used = "no_wait"
        elif constant:
            lat, wait, unserved = _simulate_constant(t, svc, int(cap_k[0]),
                                                     horizon)
            used = "constant"
        elif impl == "fast":
            raise ValueError("impl='fast' needs constant capacity or a "
                             "contention-free trace; use 'auto' or 'event'")
        else:
            lat, wait, unserved = _simulate_event(t, svc, cap_t, cap_k,
                                                  horizon)
            used = "event"

    SIM_COUNTERS["calls"] += 1
    SIM_COUNTERS["requests"] += n
    SIM_COUNTERS["seconds"] += time.perf_counter() - t0_wall
    SIM_COUNTERS[used] += 1
    return _metrics(n, lat, wait, unserved, slo)


def simulate_queue_reference(trace: RequestTrace,
                             capacity_events: Sequence[Tuple[float, int]],
                             model: ServiceTimeModel,
                             slo: SLOConfig,
                             horizon: Optional[float] = None
                             ) -> QueueMetrics:
    """The pre-vectorization implementation (golden oracle / baseline)."""
    return simulate_queue(trace, capacity_events, model, slo,
                          horizon=horizon, impl="reference")


# ------------------------------------------------------- batched (JAX)


@dataclasses.dataclass(frozen=True)
class QueueJob:
    """One cell of a batched queue simulation (``simulate_queue_batch``)."""
    trace: RequestTrace
    capacity_events: Sequence[Tuple[float, int]]
    model: ServiceTimeModel
    slo: SLOConfig
    horizon: Optional[float] = None


_JAX_CORES: "OrderedDict[tuple, object]" = OrderedDict()
_JAX_CORES_MAX = 32          # LRU bound on compiled cores per process


def _jax_modules():
    import jax
    import jax.numpy as jnp
    return jax, jnp


def _cached_core(key: tuple, build):
    core = _JAX_CORES.get(key)
    if core is None:
        core = build()
        _JAX_CORES[key] = core
        while len(_JAX_CORES) > _JAX_CORES_MAX:
            _JAX_CORES.popitem(last=False)
    else:
        _JAX_CORES.move_to_end(key)
    return core


# columns of the on-device metric fold, in order
FOLD_COLS = ("n_served", "p50_s", "p95_s", "p99_s", "mean_s", "max_s",
             "mean_wait_s", "violations")


def _device_fold(jax, jnp, lat, wait, n_valid, slo_t):
    """[n_pad] per-request arrays -> the FOLD_COLS row, on device.

    Both padded rows and unserved requests carry inf latency; padding is
    excluded from the violation count by the ``n_valid`` mask (it never
    produces *finite* latency, so the served-side stats need no mask).
    Percentiles reproduce numpy's 'linear' interpolation over the served
    (finite) prefix of the sorted latencies — but without sorting: XLA's
    CPU sort is ~40x slower than numpy's partition, so the order statistics
    are selected exactly by binary search over the float32 bit space
    (non-negative IEEE-754 floats are order-isomorphic to their integer
    bits; 31 masked-count rounds pin the k-th smallest bit-exactly,
    identically to sort-then-gather).  Only the three floor ranks are
    searched; each ceil-rank statistic is either the same value (duplicate
    run) or the smallest value strictly above it, recovered in one masked
    min pass.
    """
    served = jnp.isfinite(lat)
    m = jnp.sum(served)
    mf = m.astype(lat.dtype)
    bits = lat.view(jnp.int32)               # lat >= 0, so order-preserving
    m1 = jnp.maximum(m - 1, 0)

    # ranks lo/hi per percentile (0-indexed among ALL entries: the served
    # latencies are exactly the m smallest, inf padding sorts last)
    qs = jnp.asarray([50.0, 95.0, 99.0], dtype=lat.dtype)
    pos = jnp.maximum(mf - 1.0, 0.0) * (qs / 100.0)
    lo_r = jnp.floor(pos).astype(jnp.int32)
    hi_r = jnp.minimum(lo_r + 1, m1)

    def select(st, _):
        # invariant: kth-smallest bits in (lb, ub]; probe the midpoint
        lb, ub = st
        mid = lb + ((ub - lb) >> 1)    # lb+ub would overflow int32
        cnt = jnp.sum(bits[None, :] <= mid[:, None], axis=1)
        take = cnt >= lo_r + 1               # kth smallest <= mid
        ub = jnp.where(take, mid, ub)
        lb = jnp.where(take, lb, mid)
        return (lb, ub), None

    lb0 = jnp.full((3,), -1, dtype=jnp.int32)
    ub0 = jnp.full((3,), np.float32(np.inf).view(np.int32).item(),
                   dtype=jnp.int32)
    (_, ub), _ = jax.lax.scan(select, (lb0, ub0), None, length=31)
    lo_stat = ub.view(lat.dtype)             # [3] exact floor-rank stats
    # ceil-rank stat: ranks lo_r..(count<=lo_stat)-1 all equal lo_stat, so
    # hi_r lands on lo_stat unless it is the first strictly-larger value
    above = lat[None, :] > lo_stat[:, None]
    c_le = jnp.sum(~above, axis=1)
    next_up = jnp.min(jnp.where(above, lat[None, :], jnp.inf), axis=1)
    hi_stat = jnp.where(hi_r <= c_le - 1, lo_stat, next_up)
    frac = pos - lo_r.astype(lat.dtype)
    pcts = lo_stat * (1.0 - frac) + hi_stat * frac

    denom = jnp.maximum(mf, 1.0)
    mean = jnp.sum(jnp.where(served, lat, 0.0)) / denom
    mx = jnp.max(jnp.where(served, lat, -jnp.inf))
    mean_w = jnp.sum(jnp.where(served, wait, 0.0)) / denom
    valid = jnp.arange(lat.shape[0]) < n_valid
    viol = jnp.sum(valid & (~served | (lat > slo_t)))
    return jnp.concatenate([
        jnp.stack([mf]), pcts,
        jnp.stack([mean, mx, mean_w, viol.astype(lat.dtype)])])


def _kw_batched_core(n_pad: int, k_pad: int):
    """jit(vmap(scan)) Kiefer–Wolfowitz core for constant-capacity cells:
    [B, n_pad] traces, [B, k_pad] slot-free-time vectors (slots beyond a
    cell's k are pinned to inf), metric fold fused on device so the host
    transfer is one [B, len(FOLD_COLS)] block."""
    jax, jnp = _jax_modules()

    def build():
        def one(t, s, free0, horizon, n_valid, slo_t):
            def body(free, t_i, s_i):
                start = jnp.maximum(t_i, jnp.min(free))
                ok = start < horizon
                fin = start + s_i
                free2 = free.at[jnp.argmin(free)].set(fin)
                free = jnp.where(ok, free2, free)
                lat = jnp.where(ok, fin - t_i, jnp.inf)
                wait = jnp.where(ok, start - t_i, jnp.inf)
                return free, lat, wait

            def step(free, ts):
                t_c, s_c = ts               # [_UNROLL] requests per step
                lats, waits = [], []
                for c in range(_UNROLL):
                    free, lat, wait = body(free, t_c[c], s_c[c])
                    lats.append(lat)
                    waits.append(wait)
                return free, (jnp.stack(lats), jnp.stack(waits))

            _, (lat, wait) = jax.lax.scan(
                step, free0, (t.reshape(-1, _UNROLL),
                              s.reshape(-1, _UNROLL)))
            return _device_fold(jax, jnp, lat.reshape(-1),
                                wait.reshape(-1), n_valid, slo_t)

        return jax.jit(jax.vmap(one))

    return _cached_core(("const", n_pad, k_pad), build)


def _pw_batched_core(n_pad: int, e_pad: int, k_pad: int):
    """jit(vmap(scan)) core for piecewise capacity k(t).

    Per cell the capacity is padded step arrays [e_pad] (change times,
    slot levels, next-change times); the carry is the sorted ascending
    vector of the k_pad slot finish times plus the FIFO commit point
    ``prev_start``. Per request the earliest feasible start within
    interval e is when fewer than k_e slots are still busy — with sorted
    ``free`` that threshold is the (K - k_e)-th entry — clipped to the
    interval; the served request drops the earliest finish time (<= start
    by feasibility) and inserts its own, keeping the carry sorted.

    Unserved semantics follow the golden oracle exactly: the reference
    loop's blocked search pops the *shared* busy heap while walking
    forward, and the pops persist. Its terminal states leave the heap
    holding precisely the finish times >= horizon, so an unserved request
    whose queue-adjusted arrival is still inside the horizon zeroes every
    slot finishing before the horizon (zeros keep the carry sorted).
    """
    jax, jnp = _jax_modules()
    K = k_pad

    def build():
        def one(t, s, cap_t, cap_k, hi_t, horizon, n_valid, slo_t):
            j = jnp.arange(K)
            # loop-invariant interval tables, hoisted out of the scan
            gi = jnp.clip(K - cap_k, 0, K - 1)
            closed = cap_k <= 0

            def body(carry, t_i, s_i):
                free, prev_start = carry
                s0 = jnp.maximum(t_i, prev_start)
                thresh = jnp.where(closed, jnp.inf, free[gi])
                lo = jnp.maximum(jnp.maximum(cap_t, thresh), s0)
                cand = jnp.where(lo < hi_t, lo, jnp.inf)
                start = jnp.min(cand)
                served = start < horizon
                fin = start + s_i
                g = free[1:]
                pos = jnp.sum(g < fin)
                g_up = jnp.concatenate([g, jnp.full((1,), jnp.inf,
                                                    g.dtype)])
                g_dn = jnp.concatenate([jnp.zeros((1,), g.dtype), g])
                merged = jnp.where(j < pos, g_up,
                                   jnp.where(j == pos, fin, g_dn))
                drained = (~served) & (s0 < horizon)
                free_u = jnp.where(drained & (free < horizon), 0.0, free)
                free2 = jnp.where(served, merged, free_u)
                prev2 = jnp.where(served, start, prev_start)
                lat = jnp.where(served, fin - t_i, jnp.inf)
                wait = jnp.where(served, start - t_i, jnp.inf)
                return (free2, prev2), lat, wait

            def step(carry, ts):
                t_c, s_c = ts               # [_UNROLL] requests per step
                lats, waits = [], []
                for c in range(_UNROLL):
                    carry, lat, wait = body(carry, t_c[c], s_c[c])
                    lats.append(lat)
                    waits.append(wait)
                return carry, (jnp.stack(lats), jnp.stack(waits))

            (_, _), (lat, wait) = jax.lax.scan(
                step, (jnp.zeros((K,), t.dtype), jnp.zeros((), t.dtype)),
                (t.reshape(-1, _UNROLL), s.reshape(-1, _UNROLL)))
            return _device_fold(jax, jnp, lat.reshape(-1),
                                wait.reshape(-1), n_valid, slo_t)

        return jax.jit(jax.vmap(one))

    return _cached_core(("pw", n_pad, e_pad, k_pad), build)


# requests consumed per scan step: amortizes the fixed per-step cost of
# the XLA loop (~2-3us on CPU, which otherwise dominates small batches)
# over several Kiefer–Wolfowitz updates. n_pad is always a multiple of it.
_UNROLL = 8


def _pad_bucket(n: int, floor: int) -> int:
    """Smallest grid point >= n on the half-pow2 grid {p, 1.5p, 2p}:
    per-cell padding waste stays under 50% (above ``floor``) while cells
    of similar size share a bucket — one compiled core, one batch — and
    the number of distinct compiled shapes stays logarithmic."""
    if n <= floor:
        return floor
    p = floor
    while p * 2 < n:
        p *= 2
    if p * 3 // 2 >= n:
        return p * 3 // 2
    return p * 2


def _pad_pow2(n: int, floor: int) -> int:
    """Smallest power-of-two grid point >= n. Used for the e/k axes of
    the bucket key: padding there only adds elementwise work (values are
    invariant — padded intervals are empty, padded slots hold inf), so a
    coarser band merges more cells per bucket, and per-step loop overhead
    amortizes over a bigger batch."""
    p = floor
    while p < n:
        p *= 2
    return p


def _job_horizon(job: QueueJob) -> float:
    if job.horizon is not None:
        return float(job.horizon)
    return float(job.trace.t[-1]) + 1e9 if len(job.trace) else 0.0


def _plan(jobs: Sequence[QueueJob]):
    """Bucket jobs by kind and padded trace length; returns (buckets,
    caps) where caps[i] is job i's ``capacity_steps`` arrays.

    Only ``n_pad`` is part of the key: the on-device fold reduces over the
    n axis, so a cell's float32 metrics depend on its n_pad (reduction
    tree shape) and that must stay a pure function of the cell alone —
    shard merges must stay bit-identical to single-shot campaign runs.
    The e/k axes are padded at dispatch time to the batch maximum instead:
    padding there is exactly value-invariant per lane (padded intervals
    start at +inf and never produce a candidate, padded slots only add
    zeros below the sorted free list, and gather/min/count ops on them are
    elementwise), so co-batching cells with different e/k changes the
    compiled shape but not one bit of any lane's result."""
    buckets: Dict[tuple, List[int]] = {}
    caps: List[Optional[tuple]] = [None] * len(jobs)
    for i, job in enumerate(jobs):
        n = len(job.trace)
        if n == 0:
            continue
        cap_t, cap_k = capacity_steps(job.capacity_events,
                                      job.model.slots_per_replica)
        caps[i] = (cap_t, cap_k)
        kind = "const" if len(cap_t) == 1 else "pw"
        buckets.setdefault((kind, _pad_bucket(n, 256)), []).append(i)
    return buckets, caps


def plan_queue_buckets(jobs: Sequence[QueueJob]) -> Dict[tuple, List[int]]:
    """Public view of the shape-bucket plan: {key: [job indices]}.

    Keys are ("const", n_pad) or ("pw", n_pad); a bucket's padded element
    count is ``len(rows) * n_pad``. Jobs with empty traces are handled on
    host and appear in no bucket."""
    return _plan(jobs)[0]


def _metrics_from_fold(n: int, cols: np.ndarray,
                       slo: SLOConfig) -> QueueMetrics:
    m = int(cols[0])
    if m == 0:
        return QueueMetrics(n, 0, np.inf, np.inf, np.inf, np.inf, np.inf,
                            np.inf, 1.0, False, n)
    viol = float(cols[7]) / n
    return QueueMetrics(n, m, float(cols[1]), float(cols[2]),
                        float(cols[3]), float(cols[4]), float(cols[5]),
                        float(cols[6]), viol,
                        viol <= slo.max_violation_rate, n - m)


def simulate_queue_batch(jobs: Sequence[QueueJob], backend: str = "auto",
                         stats_out: Optional[List[str]] = None
                         ) -> List[QueueMetrics]:
    """Batched FIFO M/G/k(t) simulation over heterogeneous cells.

    Jobs are grouped into padded shape buckets and dispatched as
    ``jit(vmap(lax.scan))`` device programs — constant-capacity cells on
    the Kiefer–Wolfowitz core, piecewise-capacity cells on the k(t)-aware
    sorted-slot core — with the metric fold fused on device (float32:
    metrics agree with the exact paths to golden tolerance, not bitwise).
    ``backend='numpy'`` keeps the exact per-cell ``simulate_queue``
    dispatch. Results come back in input order; ``stats_out``, when given,
    receives one impl tag per job ("jax_batched" or "numpy"), and
    ``SERVED_ON`` counts the device-served jobs per platform."""
    if backend not in ("auto", "jax", "numpy"):
        raise ValueError(f"unknown backend {backend!r}")
    out: List[Optional[QueueMetrics]] = [None] * len(jobs)
    tags = ["numpy"] * len(jobs)
    use_jax = backend != "numpy"
    buckets, caps = _plan(jobs) if use_jax else ({}, [None] * len(jobs))
    on_device = {i for rows in buckets.values() for i in rows}
    for i, job in enumerate(jobs):
        if i not in on_device:
            out[i] = simulate_queue(job.trace, job.capacity_events,
                                    job.model, job.slo,
                                    horizon=job.horizon)
    if not buckets:
        if stats_out is not None:
            stats_out.extend(tags)
        return out  # type: ignore[return-value]

    t0_wall = time.perf_counter()
    _, jnp = _jax_modules()
    n_req = 0
    for key, rows in sorted(buckets.items()):
        kind, n_pad = key[0], key[1]
        B = len(rows)
        t_b = np.full((B, n_pad), np.inf, dtype=np.float32)
        s_b = np.zeros((B, n_pad), dtype=np.float32)
        hz = np.empty(B, dtype=np.float32)
        nv = np.empty(B, dtype=np.int32)
        st = np.empty(B, dtype=np.float32)
        for r, i in enumerate(rows):
            job = jobs[i]
            tr = job.trace
            n = len(tr)
            t_b[r, :n] = tr.t
            s_b[r, :n] = job.model.service_times(tr.prompt_tokens,
                                                 tr.decode_tokens)
            hz[r] = _job_horizon(job)
            nv[r] = n
            st[r] = job.slo.latency_target_s
        if kind == "const":
            k_pad = _pad_pow2(max(max(int(caps[i][1][0]), 1)
                                  for i in rows), 8)
            free0 = np.zeros((B, k_pad), dtype=np.float32)
            for r, i in enumerate(rows):
                free0[r, int(caps[i][1][0]):] = np.inf
            core = _kw_batched_core(n_pad, k_pad)
            res = core(jnp.asarray(t_b), jnp.asarray(s_b),
                       jnp.asarray(free0), jnp.asarray(hz),
                       jnp.asarray(nv), jnp.asarray(st))
        else:
            e_pad = -8 * (-max(len(caps[i][0]) for i in rows) // 8)
            k_pad = -8 * (-max(max(int(caps[i][1].max()), 1)
                               for i in rows) // 8)
            ct_b = np.full((B, e_pad), np.inf, dtype=np.float32)
            hi_b = np.full((B, e_pad), np.inf, dtype=np.float32)
            ck_b = np.zeros((B, e_pad), dtype=np.int32)
            for r, i in enumerate(rows):
                cap_t, cap_k = caps[i]
                e = len(cap_t)
                ct_b[r, :e] = cap_t
                ck_b[r, :e] = cap_k
                hi_b[r, :e - 1] = cap_t[1:]
            core = _pw_batched_core(n_pad, e_pad, k_pad)
            res = core(jnp.asarray(t_b), jnp.asarray(s_b),
                       jnp.asarray(ct_b), jnp.asarray(ck_b),
                       jnp.asarray(hi_b), jnp.asarray(hz),
                       jnp.asarray(nv), jnp.asarray(st))
        for dev in res.devices():
            SERVED_ON[dev.platform] = SERVED_ON.get(dev.platform, 0) + B
        res = np.asarray(res, dtype=np.float64)          # [B, FOLD_COLS]
        for r, i in enumerate(rows):
            out[i] = _metrics_from_fold(len(jobs[i].trace), res[r],
                                        jobs[i].slo)
            tags[i] = "jax_batched"
            n_req += len(jobs[i].trace)
    SIM_COUNTERS["calls"] += len(on_device)
    SIM_COUNTERS["requests"] += n_req
    SIM_COUNTERS["seconds"] += time.perf_counter() - t0_wall
    SIM_COUNTERS["jax_batched"] += len(on_device)
    if stats_out is not None:
        stats_out.extend(tags)
    return out  # type: ignore[return-value]


def simulate_queue_many(traces: Sequence[RequestTrace],
                        capacities: Sequence[Sequence[Tuple[float, int]]],
                        model: ServiceTimeModel,
                        slo: SLOConfig,
                        horizon: Optional[float] = None,
                        backend: str = "auto") -> List[QueueMetrics]:
    """Batched FIFO queue simulation over many grid cells sharing one
    model/slo/horizon — a thin wrapper over ``simulate_queue_batch``."""
    if len(traces) != len(capacities):
        raise ValueError("traces and capacities must align")
    jobs = [QueueJob(tr, ev, model, slo, horizon)
            for tr, ev in zip(traces, capacities)]
    return simulate_queue_batch(jobs, backend=backend)


# ------------------------------------------------- analytic approximation


def sakasegawa_wait(rate: float, mean_s: float, scv_s: float,
                    k_slots: int, scv_a: float = 1.0) -> float:
    """Allen–Cunneen / Sakasegawa mean-wait approximation for G/G/k.

    Wq ~= (Ca^2 + Cs^2)/2 * rho^(sqrt(2(k+1)) - 1) / (k (1 - rho)) * E[s].
    Returns inf when rho >= 1. The autoscaler inverts this numerically to
    pick the smallest k meeting the latency target.
    """
    if k_slots <= 0:
        return np.inf
    rho = rate * mean_s / k_slots
    if rho >= 1.0:
        return np.inf
    if rho <= 0.0:
        return 0.0
    return ((scv_a + scv_s) / 2.0
            * rho ** (np.sqrt(2.0 * (k_slots + 1)) - 1.0)
            / (k_slots * (1.0 - rho)) * mean_s)


def predicted_percentile_latency(rate: float, mean_s: float, scv_s: float,
                                 p99_service_s: float, k_slots: int,
                                 percentile: float = 99.0,
                                 scv_a: float = 1.0) -> float:
    """Predicted latency percentile: service tail + exponential wait tail.

    With mean wait Wq, the waiting-time tail is approximated exponential, so
    the p-th percentile of wait is -ln(1 - p/100) * Wq (4.6x Wq at p99).
    """
    wq = sakasegawa_wait(rate, mean_s, scv_s, k_slots, scv_a)
    if not np.isfinite(wq):
        return np.inf
    tail = -np.log(max(1e-12, 1.0 - percentile / 100.0))
    return p99_service_s + tail * wq
