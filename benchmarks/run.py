"""Benchmark orchestrator — one section per paper table/figure + perf.

Prints ``name,us_per_call,derived`` CSV rows (perf benches) and the
markdown tables reproducing the paper's Tables 1-2 / Figures 1-2. The perf
section additionally writes ``BENCH_perf.json`` at the repo root — the
per-PR perf trajectory (us/call, qps, index bytes, recall@10 per serving
config) that CI uploads as an artifact.

  PYTHONPATH=src python -m benchmarks.run [--only table1,fig2] [--fast]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCH_PERF_PATH = REPO_ROOT / "BENCH_perf.json"

# every serve_pipeline config row must carry both modes with these keys —
# a refactor that silently drops a bench section must fail CI, not ship a
# BENCH_perf.json that quietly stopped tracking the serving trajectory
_SERVE_MODE_KEYS = ("qps", "p50_ms", "p95_ms", "p99_ms", "worker_qps")
# live_index rows per config: the static baseline + the paged server at
# each append rate; append rows must additionally report append/swap
# telemetry and a ZERO steady-state recompile count
_LIVE_ROWS = ("static", "append_low", "append_high")
_LIVE_APPEND_KEYS = ("appended_rows", "swaps", "recompiles_steady")
# cascade Pareto rows: every operating point (baselines included) must
# report its oracle recall and saturated worker qps, at least one
# single-resolution baseline row must anchor the comparison, and the
# steady-state recompile count must be zero (fixed-nk cascade contract)
_CASCADE_ROW_KEYS = ("recall_at_10", "worker_qps", "recompiles_steady")
# fleet chaos rows: every drive reports its latency tier plus the
# droplessness/misroute accounting; the kill and rollout drives carry hard
# robustness invariants (zero lost accepted replies, zero misrouted
# replies, rollback on regression, p99 held vs the healthy baseline)
_FLEET_ROWS = ("healthy", "kill_restart", "bad_rollout")
_FLEET_ROW_KEYS = ("n", "n_ok", "p50_ms", "p99_ms", "lost_accepted",
                   "misrouted", "health_ok")
# paged rows per config: both append rates, every row with
# full serve telemetry and a ZERO steady-state recompile count (paged
# appends are page-pointer swaps at fixed dispatch shapes); the depth sweep
# must cover {1,2,4} with a residency bit-identity check per depth, and
# the oversubscription headline row must hold >=80% of the fully-resident
# qps at pipeline depth >=2
_PAGED_ROWS = ("paged_append_0", "paged_append_high")
_PAGED_DEPTHS = ("depth_1", "depth_2", "depth_4")
_PAGED_OVERSUB_FLOOR = 0.80


def check_perf_schema(results: dict) -> None:
    """Validate the perf dict before it becomes ``BENCH_perf.json``."""
    sp = results.get("serve_pipeline")
    if not isinstance(sp, dict) or not isinstance(sp.get("configs"), dict) \
            or not sp["configs"]:
        raise SystemExit("BENCH_perf.json schema: missing or empty "
                         "'serve_pipeline.configs' section")
    for name, row in sp["configs"].items():
        for mode in ("sync", "pipelined"):
            if mode not in row:
                raise SystemExit(f"serve_pipeline.{name}: missing '{mode}' row")
            missing = [k for k in _SERVE_MODE_KEYS if k not in row[mode]]
            if missing:
                raise SystemExit(f"serve_pipeline.{name}.{mode}: missing "
                                 f"keys {missing}")
        if "match" not in row:
            raise SystemExit(f"serve_pipeline.{name}: missing sync-vs-"
                             f"pipelined 'match' flag")
        if not row["match"]:
            raise SystemExit(f"serve_pipeline.{name}: pipelined results "
                             f"diverged from the sync path (match=False)")
    li = results.get("live_index")
    if not isinstance(li, dict) or not isinstance(li.get("configs"), dict) \
            or not li["configs"]:
        raise SystemExit("BENCH_perf.json schema: missing or empty "
                         "'live_index.configs' section")
    for name, cfg in li["configs"].items():
        for rowname in _LIVE_ROWS:
            if rowname not in cfg:
                raise SystemExit(f"live_index.{name}: missing "
                                 f"'{rowname}' row")
            missing = [k for k in _SERVE_MODE_KEYS if k not in cfg[rowname]]
            if missing:
                raise SystemExit(f"live_index.{name}.{rowname}: missing "
                                 f"keys {missing}")
            if rowname.startswith("append"):
                missing = [k for k in _LIVE_APPEND_KEYS
                           if k not in cfg[rowname]]
                if missing:
                    raise SystemExit(f"live_index.{name}.{rowname}: missing "
                                     f"keys {missing}")
                if cfg[rowname]["recompiles_steady"] != 0:
                    raise SystemExit(
                        f"live_index.{name}.{rowname}: "
                        f"{cfg[rowname]['recompiles_steady']} steady-state "
                        f"recompiles — appends must never stall serving on "
                        f"a jit compile (fixed-shape paged dispatch "
                        f"contract)")
    ca = results.get("cascade")
    if not isinstance(ca, dict) or not isinstance(ca.get("rows"), dict) \
            or not ca["rows"]:
        raise SystemExit("BENCH_perf.json schema: missing or empty "
                         "'cascade.rows' section")
    if not any(row.get("baseline") for row in ca["rows"].values()):
        raise SystemExit("cascade: no single-resolution baseline row — "
                         "the Pareto sweep has lost its reference point")
    for name, row in ca["rows"].items():
        missing = [k for k in _CASCADE_ROW_KEYS if k not in row]
        if missing:
            raise SystemExit(f"cascade.{name}: missing keys {missing}")
        if row["recompiles_steady"] != 0:
            raise SystemExit(
                f"cascade.{name}: {row['recompiles_steady']} steady-state "
                f"recompiles — with nk fixed, every cascade dispatch must "
                f"reuse its compiled shape")
    pg = results.get("paged")
    if not isinstance(pg, dict) or not isinstance(pg.get("configs"), dict) \
            or not pg["configs"]:
        raise SystemExit("BENCH_perf.json schema: missing or empty "
                         "'paged.configs' section")
    for name, cfg in pg["configs"].items():
        for rowname in _PAGED_ROWS:
            if rowname not in cfg:
                raise SystemExit(f"paged.{name}: missing '{rowname}' row")
            row = cfg[rowname]
            missing = [k for k in _SERVE_MODE_KEYS + _LIVE_APPEND_KEYS
                       if k not in row]
            if missing:
                raise SystemExit(f"paged.{name}.{rowname}: missing keys "
                                 f"{missing}")
            if row["recompiles_steady"] != 0:
                raise SystemExit(
                    f"paged.{name}.{rowname}: "
                    f"{row['recompiles_steady']} steady-state recompiles — "
                    f"page-pointer appends must never stall serving on a "
                    f"jit compile (paged fixed-shape dispatch contract)")
    ds = pg.get("depth_sweep")
    if not isinstance(ds, dict):
        raise SystemExit("paged: missing 'depth_sweep' section")
    for dname in _PAGED_DEPTHS:
        drow = ds.get(dname)
        if not isinstance(drow, dict) or "resident" not in drow \
                or "oversubscribed" not in drow:
            raise SystemExit(f"paged.depth_sweep.{dname}: missing "
                             f"resident/oversubscribed rows")
        if not drow.get("match"):
            raise SystemExit(
                f"paged.depth_sweep.{dname}: oversubscribed results "
                f"diverged from fully resident (match=False) — host-tier "
                f"streaming must change throughput, never results")
        if drow["oversubscribed"]["host_pages"] == 0:
            raise SystemExit(f"paged.depth_sweep.{dname}: oversubscribed "
                             f"row has no host-tier pages — the pool cap "
                             f"did not oversubscribe")
    ov = pg.get("oversubscription")
    if not isinstance(ov, dict) or "ratio" not in ov:
        raise SystemExit("paged: missing 'oversubscription' row")
    if ov.get("depth", 0) < 2:
        raise SystemExit("paged.oversubscription: headline row must come "
                         "from pipeline depth >= 2")
    if ov["ratio"] < _PAGED_OVERSUB_FLOOR:
        raise SystemExit(
            f"paged.oversubscription: {ov['ratio']:.2f} of fully-resident "
            f"qps with {ov.get('host_pages')} host pages — below the "
            f"{_PAGED_OVERSUB_FLOOR:.2f} floor; host-tier staging is not "
            f"hiding behind compute at depth {ov.get('depth')}")
    sw = pg.get("page_count_sweep")
    if not isinstance(sw, dict) or "recompiles_steady" not in sw:
        raise SystemExit("paged: missing 'page_count_sweep' section")
    if len(set(sw.get("page_counts", []))) < 2:
        raise SystemExit("paged.page_count_sweep: page count never "
                         "changed — the sweep is not sweeping")
    if sw["recompiles_steady"] != 0:
        raise SystemExit(
            f"paged.page_count_sweep: {sw['recompiles_steady']} "
            f"steady-state recompiles across page counts "
            f"{sw.get('page_counts')} — [lo,hi) is traced, page count is "
            f"data; growth must never leak into a static jit key")
    ga = pg.get("guard_ab")
    if not isinstance(ga, dict) or not ga.get("bitwise_identical"):
        raise SystemExit("paged.guard_ab: per-row guard results are not "
                         "bit-identical to the whole-batch guard — the "
                         "guard is an optimisation, never a result change")
    fl = results.get("fleet")
    if not isinstance(fl, dict):
        raise SystemExit("BENCH_perf.json schema: missing 'fleet' section")
    for rowname in _FLEET_ROWS:
        row = fl.get(rowname)
        if not isinstance(row, dict):
            raise SystemExit(f"fleet: missing '{rowname}' drive row")
        missing = [k for k in _FLEET_ROW_KEYS if k not in row]
        if missing:
            raise SystemExit(f"fleet.{rowname}: missing keys {missing}")
        if row["lost_accepted"] != 0:
            raise SystemExit(
                f"fleet.{rowname}: {row['lost_accepted']} accepted replies "
                f"never got a terminal payload — the router dropped "
                f"accepted work (droplessness invariant)")
        if row["misrouted"] != 0:
            raise SystemExit(
                f"fleet.{rowname}: {row['misrouted']} replies answered with "
                f"wrong ids — a reply was served by an unvalidated or "
                f"stale index (misroute invariant)")
        if not row["health_ok"]:
            raise SystemExit(f"fleet.{rowname}: fleet unhealthy after the "
                             f"drive (a replica never rejoined, or a "
                             f"background maintenance thread died)")
    if not fl["bad_rollout"].get("rolled_back"):
        raise SystemExit("fleet.bad_rollout: the recall-regressing rollout "
                         "was NOT rolled back — the health gate is dead")
    p99_healthy = fl["healthy"]["p99_ms"]
    p99_kill = fl["kill_restart"]["p99_ms"]
    if p99_kill > 2.0 * max(p99_healthy, 1.0):
        raise SystemExit(
            f"fleet.kill_restart: p99 {p99_kill:.1f}ms vs healthy "
            f"{p99_healthy:.1f}ms — a single replica kill/restart must not "
            f"double the latency tier (failover is supposed to contain it)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: table1,table2,fig1,fig2,perf,size")
    ap.add_argument("--fast", action="store_true",
                    help="smaller corpora (CI-sized)")
    ap.add_argument("--host-devices", type=int, default=4,
                    help="CPU device count for the sharded perf configs")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    def need(name):
        return only is None or name in only

    if need("perf"):
        # multi-device CPU mesh for the sharded sweep configs; must happen
        # before the bench imports below pull in jax and touch a device
        from repro.util import force_host_device_count
        force_host_device_count(args.host_devices)

    import benchmarks.common as common
    import benchmarks.perf_qps as perf_qps
    if args.fast:
        common.N_DOCS = 4000
        common.DIM = 256
        perf_qps.N_DOCS = 4000
        perf_qps.DIM = 256

    t0 = time.time()
    datasets = None

    if need("table1") or need("table2") or need("fig1") or need("fig2"):
        print(f"# building {3} corpora (n={common.N_DOCS}, d={common.DIM})",
              flush=True)
        datasets = common.load_all_datasets(common.N_DOCS, common.DIM)

    if need("table1"):
        from benchmarks.table1_indomain import run as t1
        t1(datasets)
    if need("table2"):
        from benchmarks.table2_ood import run as t2
        t2(datasets)
    if need("fig1"):
        from benchmarks.fig1_cutoff import run as f1
        f1(datasets)
    if need("fig2"):
        from benchmarks.fig2_nembed import run as f2
        f2(datasets)
    if need("perf"):
        print("\n### Perf — name,us_per_call,derived")
        results = perf_qps.run()
        check_perf_schema(results)
        BENCH_PERF_PATH.write_text(json.dumps(results, indent=2,
                                              sort_keys=True) + "\n")
        print(f"# wrote {BENCH_PERF_PATH}")
    if need("size"):
        print("\n### Index size — name,us_per_call,derived")
        from benchmarks.index_size import run as isz
        isz()

    print(f"\n# benchmarks done in {time.time()-t0:.1f}s", flush=True)


if __name__ == "__main__":
    main()
