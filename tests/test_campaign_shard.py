"""Sharded / resumable campaign execution: cell keys, spools, merge.

The acceptance bar: --shard 0/2 + --shard 1/2 + merge must reproduce the
single-shot artifact's reductions *exactly*, and --resume must re-execute
only the missing cells.
"""
import dataclasses
import hashlib
import json

import pytest

from repro.workloads.campaign import (REDUCE_KEYS, SCHEMA, ScenarioCell,
                                      make_grid, merge_spools,
                                      reduce_metrics, run_campaign,
                                      shard_cells, spool_append, spool_load)

# a fast 4-cell grid (short horizon) for end-to-end runs
FAST_CELLS = [
    ScenarioCell(preempt=p, scheduler="first_fit", arrival=a,
                 total_nodes=48, slo_target_s=30.0, horizon_s=1800.0,
                 n_jobs=20, rate_rps=1.0)
    for p in ("kill", "checkpoint")
    for a in ("poisson", "flash_crowd")
]


# ------------------------------------------------------------- cell keys


def test_cell_key_covers_all_fields():
    """Regression: rate_rps / horizon_s / n_jobs / st_max_nodes were not in
    cell_id, so custom grids varying them collided — the spool key must
    hash every field."""
    base = ScenarioCell(preempt="kill", scheduler="first_fit",
                        arrival="poisson", total_nodes=48,
                        slo_target_s=30.0)
    for field in ("rate_rps", "horizon_s", "n_jobs", "st_max_nodes",
                  "preempt", "arrival", "total_nodes", "slo_target_s",
                  "policy", "mix", "budget", "queue_impl", "seed"):
        bumped = {"rate_rps": 3.5, "horizon_s": 999.0, "n_jobs": 7,
                  "st_max_nodes": 5, "preempt": "checkpoint",
                  "arrival": "mmpp", "total_nodes": 49,
                  "slo_target_s": 31.0, "policy": "demand_capped",
                  "mix": "2hpc2ws", "budget": 5000.0,
                  "queue_impl": "exact", "seed": 1}[field]
        other = dataclasses.replace(base, **{field: bumped})
        assert other.cell_key() != base.cell_key(), field
        assert other.cell_id() != base.cell_id(), field


def test_cell_key_deterministic_and_grid_unique():
    cells = make_grid("small") + make_grid("mix_tiny")
    keys = [c.cell_key() for c in cells]
    assert len(set(keys)) == len(cells)
    assert keys == [c.cell_key() for c in cells]        # stable


def test_shard_cells_partition_is_exact():
    cells = make_grid("small")
    parts = [shard_cells(cells, f"{i}/3") for i in range(3)]
    flat = [c for p in parts for c in p]
    assert sorted(c.cell_key() for c in flat) == \
        sorted(c.cell_key() for c in cells)
    assert all(len(p) >= len(cells) // 3 for p in parts)
    with pytest.raises(ValueError):
        shard_cells(cells, "3/3")
    with pytest.raises(ValueError):
        shard_cells(cells, "bogus")


# ---------------------------------------------------------------- spools


def test_spool_roundtrip_and_torn_line(tmp_path):
    path = str(tmp_path / "s.jsonl")
    rows = [{"cell_key": f"k{i}", "metrics": {"completed": i}}
            for i in range(3)]
    for r in rows:
        spool_append(path, r)
    with open(path, "a") as f:
        f.write('{"cell_key": "torn", "metr')        # killed mid-write
    loaded = spool_load(path)
    assert set(loaded) == {"k0", "k1", "k2"}
    assert loaded["k2"]["metrics"]["completed"] == 2


# ------------------------------------------------------- shard + merge


def test_shard_merge_reproduces_single_shot(tmp_path):
    single = run_campaign(FAST_CELLS, workers=1, grid_name="unit")
    spools = []
    for i in range(2):
        sp = str(tmp_path / f"s{i}.jsonl")
        spools.append(sp)
        run_campaign(FAST_CELLS, workers=1, grid_name="unit",
                     spool_path=sp, shard=f"{i}/2")
    merged, missing = merge_spools(spools, grid_cells=FAST_CELLS,
                                   grid_name="unit")
    assert missing == []
    assert merged["reductions"] == single["reductions"]
    assert [c["cell_key"] for c in merged["cells"]] == \
        [c["cell_key"] for c in single["cells"]]
    # non-timing metrics identical cell by cell
    for a, b in zip(single["cells"], merged["cells"]):
        for k in REDUCE_KEYS:
            assert a["metrics"][k] == b["metrics"][k], k


def test_merge_reports_missing_cells(tmp_path):
    sp = str(tmp_path / "s0.jsonl")
    run_campaign(FAST_CELLS, workers=1, spool_path=sp, shard="0/2")
    merged, missing = merge_spools([sp], grid_cells=FAST_CELLS)
    assert len(missing) == 2
    assert merged["n_cells"] == 2


def test_resume_runs_only_missing_cells(tmp_path):
    sp = str(tmp_path / "s.jsonl")
    # "interrupted" run: only shard 0's cells made it to the spool
    run_campaign(FAST_CELLS, workers=1, spool_path=sp, shard="0/2")
    art = run_campaign(FAST_CELLS, workers=1, spool_path=sp, resume=True,
                       grid_name="unit")
    assert art["throughput"]["skipped"] == 2
    assert art["throughput"]["executed"] == 2
    assert art["n_cells"] == 4
    # second resume: nothing left to do
    art2 = run_campaign(FAST_CELLS, workers=1, spool_path=sp, resume=True,
                        grid_name="unit")
    assert art2["throughput"]["executed"] == 0
    assert art2["throughput"]["skipped"] == 4
    assert art2["reductions"] == art["reductions"]


def test_run_campaign_writes_v7_artifact(tmp_path):
    out = tmp_path / "c.json"
    art = run_campaign(FAST_CELLS[:2], workers=1, out_path=str(out),
                       grid_name="unit")
    disk = json.loads(out.read_text())
    assert disk["schema"] == "phoenix-campaign-v7"
    assert "throughput" in disk and disk["throughput"]["executed"] == 2
    assert disk["cells"][0]["queue_sim"]["requests"] > 0
    assert disk["cells"][0]["metrics"]["queue_sim_s"] >= 0.0
    assert art["reductions"] == disk["reductions"]
    # v6: per-impl attribution on the row and aggregated in throughput
    assert disk["cells"][0]["queue_impl"] == "batched"
    impls = disk["throughput"]["queue_impls"]
    assert sum(impls.values()) >= 2 and "jax_batched" in impls


# ------------------------------------------------- v5 market artifact path

# market cells: budget engines over the non-degenerate tenant path, short
# horizon so the end-to-end shard+merge stays fast
MARKET_CELLS = [
    ScenarioCell(preempt="kill", scheduler="first_fit", arrival="poisson",
                 total_nodes=48, slo_target_s=30.0, horizon_s=1800.0,
                 n_jobs=20, rate_rps=1.0, policy=pol, budget=2000.0)
    for pol in ("budget_auction", "second_price")
]


def test_merge_refuses_stale_schema_spools(tmp_path):
    """Spools written under an older artifact schema hash to different
    cell keys, so a merge against the current grid reports every cell
    missing instead of silently folding stale rows in."""
    def old_key(cell):
        blob = json.dumps({"schema": "phoenix-campaign-v4",
                           **dataclasses.asdict(cell)}, sort_keys=True)
        return hashlib.sha256(blob.encode()).hexdigest()[:16]

    sp = str(tmp_path / "stale.jsonl")
    for c in FAST_CELLS:
        spool_append(sp, {"cell_key": old_key(c), "cell_id": c.cell_id(),
                          "metrics": {"completed": 1}})
    merged, missing = merge_spools([sp], grid_cells=FAST_CELLS)
    assert len(missing) == len(FAST_CELLS)
    assert merged["n_cells"] == 0
    # while a current-schema spool folds cleanly
    assert old_key(FAST_CELLS[0]) != FAST_CELLS[0].cell_key()
    assert SCHEMA == "phoenix-campaign-v7"


def test_market_policy_state_survives_shard_merge_bit_for_bit(tmp_path):
    """The v5 market fields (budgets, spend ledger, clearing prices in
    per-cell policy_state and spend/budget_remaining in tenant_metrics)
    must reduce identically through shard+merge and a single-shot run."""
    single = run_campaign(MARKET_CELLS, workers=1, grid_name="unit")
    spools = []
    for i in range(2):
        sp = str(tmp_path / f"m{i}.jsonl")
        spools.append(sp)
        run_campaign(MARKET_CELLS, workers=1, grid_name="unit",
                     spool_path=sp, shard=f"{i}/2")
    merged, missing = merge_spools(spools, grid_cells=MARKET_CELLS,
                                   grid_name="unit")
    assert missing == []
    for a, b in zip(single["cells"], merged["cells"]):
        assert a["cell_key"] == b["cell_key"]
        # market state bit-for-bit through the JSONL spool round-trip
        assert json.dumps(a["policy_state"], sort_keys=True, default=float) \
            == json.dumps(b["policy_state"], sort_keys=True, default=float)
        assert a["tenant_metrics"] == b["tenant_metrics"]
        ps = a["policy_state"]
        assert ps["engine"] in ("budget_auction", "second_price")
        market = ps["market"]
        assert market["transactions"] > 0
        for name, spent in market["spend"].items():
            declared = market["budgets"][name]
            assert declared == 2000.0
            assert 0.0 <= spent <= declared + 1e-6
        spends = {n: t["spend"] for n, t in a["tenant_metrics"].items()}
        assert spends == {n: market["spend"].get(n, 0.0)
                          for n in spends}, a["cell_id"]
    assert merged["reductions"] == single["reductions"]


# ------------------------------------------------- inf-masked reductions


def _row(key, p99, slo_met=False, unserved=0):
    m = {k: 1.0 for k in
         ("completed", "killed", "preemptions", "avg_turnaround_s",
          "ws_p50_s", "ws_p95_s", "ws_violation_rate",
          "ws_unmet_node_seconds", "ws_peak_nodes", "st_avg_alloc",
          "ws_avg_alloc", "queue_sim_s", "wall_s")}
    m["ws_p99_s"] = p99
    m["ws_unserved"] = unserved
    return {"preempt": "kill", "scheduler": "first_fit",
            "arrival": "poisson", "total_nodes": 48, "slo_target_s": 30.0,
            "policy": "paper", "mix": "paper2", "budget": 0.0,
            "cell_id": key, "cell_key": key, "slo_met": slo_met,
            "metrics": m}


def test_reduce_metrics_masks_inf_and_reports_rate():
    """Regression: one starved cell (inf percentiles) used to poison every
    marginal mean containing it."""
    rows = [_row("a", 10.0, slo_met=True), _row("b", 20.0, slo_met=True),
            _row("c", float("inf"), unserved=5)]
    red = reduce_metrics(rows)
    ov = red["overall"]
    assert ov["ws_p99_s"] == pytest.approx(15.0)        # finite-masked mean
    assert ov["inf_rate"] == pytest.approx(1.0 / 3.0)
    assert ov["cells"] == 3
    assert ov["ws_unserved"] == pytest.approx(5.0 / 3.0)


def test_reduce_metrics_all_inf_column_stays_inf():
    rows = [_row("a", float("inf"), unserved=3),
            _row("b", float("inf"), unserved=4)]
    ov = reduce_metrics(rows)["overall"]
    assert ov["ws_p99_s"] == float("inf")
    assert ov["inf_rate"] == 1.0


def test_reduce_metrics_order_independent():
    rows = [_row(k, p) for k, p in
            (("a", 10.0), ("b", 20.0), ("c", 30.0), ("d", 40.0))]
    fwd = reduce_metrics(list(rows))
    rev = reduce_metrics(list(reversed(rows)))
    assert fwd == rev


# ------------------------------------------------------ one chip owner


def test_flush_platform_reads_the_pinned_platform():
    import jax
    from repro.workloads.campaign import _flush_platform
    assert _flush_platform() == jax.default_backend() == "cpu"


def test_forked_workers_refused_when_the_flush_runs_on_an_accelerator(
        monkeypatch):
    """An accelerator belongs to one process: with the queue flush on a
    TPU, workers > 1 is an error before any cell runs, and workers=1
    still runs."""
    import repro.workloads.campaign as campaign
    cells = [dataclasses.replace(FAST_CELLS[0], seed=s)
             for s in range(campaign.QUEUE_CHUNK + 1)]
    ran = []
    monkeypatch.setattr(campaign, "_flush_platform", lambda: "tpu")
    monkeypatch.setattr(campaign, "run_cell_chunk",
                        lambda ch, trace_dir=None: ran.append(ch) or [])
    with pytest.raises(RuntimeError, match="workers=1"):
        run_campaign(cells, workers=2)
    assert ran == []
    run_campaign(cells, workers=1)
    assert sum(len(ch) for ch in ran) == len(cells)
