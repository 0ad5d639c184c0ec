"""Integration tests for the consolidation simulator + paper-claim checks."""
import dataclasses

import pytest

from repro.core.experiment import (DC_SIZES, SC_TOTAL, run_dynamic,
                                   run_experiment, run_static, validate_claims)
from repro.core.simulator import ConsolidationSim
from repro.core.traces import (TWO_WEEKS_S, synthetic_sdsc_blue,
                               worldcup_demand_events)
from repro.core.types import Job, JobState, SimConfig, paper_tenants

DAY = 86400.0


@pytest.fixture(scope="module")
def small_world():
    jobs = synthetic_sdsc_blue(seed=1, n_jobs=300, horizon=2 * DAY)
    ws = worldcup_demand_events(seed=1, horizon=2 * DAY)
    return jobs, ws


def test_deterministic(small_world):
    jobs, ws = small_world
    r1 = run_dynamic(jobs, ws, 160, horizon=2 * DAY)
    r2 = run_dynamic(jobs, ws, 160, horizon=2 * DAY)
    assert r1.completed == r2.completed
    assert r1.killed == r2.killed
    assert r1.avg_turnaround == pytest.approx(r2.avg_turnaround)


def test_ws_demand_always_met_when_capacity_suffices(small_world):
    jobs, ws = small_world
    r = run_dynamic(jobs, ws, 160, horizon=2 * DAY)
    assert r.ws_unmet_node_seconds == 0.0


def test_turnaround_at_least_runtime(small_world):
    jobs, ws = small_world
    cfg = SimConfig(total_nodes=160)
    sim = ConsolidationSim(cfg, tenants=paper_tenants(jobs, ws),
                           horizon=2 * DAY)
    sim.run()
    for j in sim.jobs:
        if j.state is JobState.COMPLETED:
            assert j.turnaround >= j.runtime - 1e-6


def test_more_nodes_never_hurt_completed(small_world):
    jobs, ws = small_world
    r_small = run_dynamic(jobs, ws, 150, horizon=2 * DAY)
    r_big = run_dynamic(jobs, ws, 200, horizon=2 * DAY)
    assert r_big.completed >= r_small.completed - 5  # small jitter tolerated


def test_checkpoint_mode_dominates_kill_mode(small_world):
    """Beyond-paper: checkpoint-preemption completes at least as many jobs."""
    jobs, ws = small_world
    kill = run_dynamic(jobs, ws, 160, horizon=2 * DAY)
    ck = run_dynamic(jobs, ws, 160, horizon=2 * DAY,
                     cfg=SimConfig(preempt_mode="checkpoint"))
    assert ck.killed == 0
    assert ck.completed >= kill.completed


def test_paper_claims_full_experiment():
    """The paper's §III-D claims on the full 2-week calibrated traces."""
    res = run_experiment(seed=0)
    claims = validate_claims(res)
    assert claims["dc160_completed_ge_sc"], claims
    assert claims["dc160_user_benefit_ge_sc"], claims
    assert claims["ws_demand_always_met"], claims
    assert claims["killed_grows_as_cluster_shrinks"], claims
    assert claims["cost_ratio_at_160"] == pytest.approx(160 / 208)


def test_node_failures_shrink_capacity_but_run(small_world):
    jobs, ws = small_world
    cfg = SimConfig(total_nodes=160, node_mtbf=50 * DAY,
                    node_repair_time=3600.0)
    r = run_dynamic(jobs, ws, 160, horizon=2 * DAY, cfg=cfg)
    assert r.completed > 0


def test_straggler_mitigation_improves_turnaround(small_world):
    jobs, ws = small_world
    slow = run_dynamic(jobs, ws, 180, horizon=2 * DAY, cfg=SimConfig(
        straggler_frac=0.15, straggler_slowdown=3.0,
        speculative_relaunch=False))
    spec = run_dynamic(jobs, ws, 180, horizon=2 * DAY, cfg=SimConfig(
        straggler_frac=0.15, straggler_slowdown=3.0,
        speculative_relaunch=True))
    assert spec.avg_turnaround <= slow.avg_turnaround


def test_easy_backfill_not_worse_than_fcfs(small_world):
    jobs, ws = small_world
    fcfs = run_dynamic(jobs, ws, 160, horizon=2 * DAY,
                       cfg=SimConfig(scheduler="fcfs"))
    easy = run_dynamic(jobs, ws, 160, horizon=2 * DAY,
                       cfg=SimConfig(scheduler="easy_backfill"))
    assert easy.completed >= fcfs.completed
