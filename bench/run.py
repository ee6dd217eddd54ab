"""Chip benchmark of the served retrieval path.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process on the machine that holds the cell's chips. It makes the
corpus, the PCA cut and the index on the device from ``--seed``, starts
the program's ``RetrievalServer`` and compiles its one dispatch shape
(set-up), drives the cell's traffic into ``submit`` for ``--seconds``,
then checks a sample of the answers against an exact reference. The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics read from a profiler trace of the
window), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``, each number compared beside its limit. The same numbers are
the last lines of standard error.

It exits non-zero, and prints no result, off a TPU, on a device kind that
``bench/peaks.json`` lacks, or with fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

from harness import spec  # noqa: E402

SAMPLE = 512          # answers checked against the reference per run
GIB = float(1 << 30)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def chips(count: int):
    """The first ``count`` devices, which must be TPU chips of a kind in
    the peaks table."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU (JAX platform is "
                         f"{devs[0].platform!r}); nothing was measured")
    if len(devs) < count:
        raise SystemExit(f"bench: the cell needs {count} chips, JAX finds "
                         f"{len(devs)}; nothing was measured")
    spec.peaks(devs[0].device_kind)
    return devs[:count]


COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


def configure_jax():
    """The persistent compile cache in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), every compile cached."""
    import jax
    from repro.util import compile_cache_dir
    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax


def compile_counter():
    """Counts jaxpr traces and backend compiles from now on."""
    import jax
    seen = {"n": 0, "on": True}

    def listen(event, duration, **_):
        if seen["on"] and event in COMPILE_EVENTS:
            seen["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    return seen


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def end_to_end(cell: dict, arr: dict, t0: float, seconds: float,
               setup_s: float, peak: int) -> dict:
    """The cell's end-to-end metrics from the request log."""
    t_end = t0 + seconds
    in_window = arr["ok"] & (arr["t_done"] >= t0) & (arr["t_done"] <= t_end)
    due = (arr["t_sched"] >= t0) & (arr["t_sched"] <= t_end)
    lat_ms = np.where(arr["ok"], arr["t_done"] - arr["t_sched"],
                      np.inf)[due] * 1e3
    values = dict(
        qps=float(in_window.sum() / seconds),
        p50_ms=float(np.percentile(lat_ms, 50)) if lat_ms.size else None,
        p99_ms=float(np.percentile(lat_ms, 99)) if lat_ms.size else None,
        peak_hbm_gib=peak / GIB, setup_s=setup_s)
    return {m["name"]: dict(value=values[m["name"]], unit=m["unit"])
            for m in cell["end_to_end"]}


def per_layer(cell: dict, facts: dict) -> dict:
    out = {}
    for m in cell["per_layer"]:
        v = spec.reader(m["name"], cell["root"])(facts)
        if v is not None:
            out[m["name"]] = dict(value=float(v), unit=m["unit"])
    return out


def sample(log_, arr: dict, Q: np.ndarray, seed: int):
    """A seeded sample of the answered requests: (queries, scores, ids)."""
    ok = np.flatnonzero(arr["ok"])
    pick = np.sort(np.random.default_rng(seed).choice(
        ok, size=min(SAMPLE, len(ok)), replace=False))
    if not len(pick):
        return Q[:0], np.zeros((0, 0)), np.zeros((0, 0), np.int64)
    return (Q[arr["query"][pick]],
            np.stack([log_.payload[i][0] for i in pick]),
            np.stack([log_.payload[i][1] for i in pick]))


def check(exact, Q: np.ndarray, log_, arr: dict, limits: dict,
          seed: int) -> tuple[bool, dict]:
    """Readings of a seeded sample of the answered requests against the
    exact reference, each beside its limit."""
    Qs, scores, ids = sample(log_, arr, Q, seed)
    r = exact(Qs).read(scores, ids) if len(Qs) else dict(
        bad_ids=0, uncovered=0, score_err=0.0, rank_gap=0.0)
    checks = dict(
        failed=dict(value=int((~arr["ok"]).sum()), limit=0),
        checked=dict(value=int(len(Qs)), limit=SAMPLE),
        bad_ids=dict(value=r["bad_ids"], limit=0),
        uncovered=dict(value=r["uncovered"], limit=0),
        score_err=dict(value=r["score_err"], limit=limits["score_err"]),
        rank_gap=dict(value=r["rank_gap"], limit=limits["rank_gap"]))
    correct = (len(Qs) > 0
               and all(c["value"] <= c["limit"] for name, c in checks.items()
                       if name != "checked"))
    return correct, checks


def main(argv=None, root: str = spec.ROOT) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload, root)
    cfg, mix = cell["config"], cell["traffic"]

    jax = configure_jax()
    devices = chips(cfg["chips"])
    dev = devices[0]
    log(f"[device] {dev.platform} {dev.device_kind} x {len(devices)} "
        f"(of {jax.device_count()}), jax {jax.__version__}")

    from harness import reference, system, traffic, xtrace
    from harness.corpus import Corpus
    corpus = Corpus(cfg, args.seed)
    t = time.perf_counter()
    index, pruner = system.build(cfg, corpus, devices)
    log(f"[setup] index {tuple(index.vectors.shape)} {index.vectors.dtype} "
        f"built in {time.perf_counter() - t:.3f} s; set-up peak "
        f"{peak_bytes(devices)} B")
    Q = corpus.queries(mix["query_pool"], dev)
    busiest = system.index_bytes(index)
    server = system.serve(index, pruner, cfg["k"])
    compiles = compile_counter()
    setup_s = time.perf_counter() - T_START
    log(f"[setup] {setup_s:.3f} s")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace \
        else None
    if trace_dir:
        # runtime events only: the Python tracer would slow the generator
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.perf_counter()
    log_ = traffic.LOOPS[mix["loop"]](server, Q, mix, t0, args.seconds,
                                      args.seed)
    t_close = time.perf_counter()
    if trace_dir:
        jax.profiler.stop_trace()
    compiles["on"] = False
    server.close()
    log(f"[window] {len(log_.replies)} requests; compilations in the "
        f"window: {compiles['n']}; drained {t_close - t0 - args.seconds:.3f}"
        f" s after the close")
    peak = peak_bytes(devices)
    arr = log_.arrays()
    t_end = t0 + args.seconds
    batches = [b for b in server.batch_log if t0 <= b[2] <= t_end]

    result = dict(correct=False, attempted=int(len(arr["ok"])),
                  failed=int((~arr["ok"]).sum()))
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=len(devices), memory_peak_bytes=peak)
    if trace_dir:
        # the window opens with the first dispatch the traffic caused
        red = xtrace.reduce(xtrace.find(trace_dir), seconds=args.seconds)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if not red["devices"]:
            raise SystemExit("bench: the trace holds no device operation")
        busy = [d["busy_ns"] / 1e9 for d in red["devices"]]
        facts = dict(trace=red, batches=batches, seconds=args.seconds,
                     max_batch=server.max_batch, config=cfg,
                     peaks=spec.peaks(dev.device_kind), index_bytes=busiest,
                     gen_lag_s=arr["t_send"] - arr["t_sched"])
        result["metrics"] = per_layer(cell, facts)
        device.update(busy_s=float(np.mean(busy)),
                      window_s=red["window_ns"] / 1e9)
        result["breakdown"] = dict(device_ops=xtrace.top_ops(red),
                                   idle_gaps=xtrace.top_gaps(red))
    else:
        result["metrics"] = end_to_end(cell, arr, t0, args.seconds, setup_s,
                                       peak)
    result["device"] = device

    del server, index, pruner
    gc.collect()
    t = time.perf_counter()
    correct, checks = check(
        lambda q: reference.Exact(corpus, dev, q, cfg["k"]), Q, log_, arr,
        cfg["limits"], args.seed)
    log(f"[reference] {time.perf_counter() - t:.3f} s")
    result["correct"] = bool(correct)
    result["checks"] = checks
    for name, c in checks.items():
        log(f"{name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
