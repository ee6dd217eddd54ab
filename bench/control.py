"""Readings that the limits of ``correct`` are set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 3

For each seed, in one process: the cell's set-up, a short window of its
own traffic through the program, then the sample of answers that a run
checks, read against the exact reference (the sound reading), and the
control read the same way: the reference in the program's place one
precision step below the configuration's (``high`` for float32 at
``highest``, ``int4`` for int8 rows; int8 cells also read ``high``). One
JSON line per seed, then the largest sound and the smallest control
reading of each number. The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run  # noqa: E402  (sets the import path of the program too)
from harness import spec  # noqa: E402


def main(argv=None, root: str = spec.ROOT) -> list[dict]:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default=None, help="also append lines here")
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload, root)
    cfg, mix = cell["config"], cell["traffic"]

    run.configure_jax()
    devices = run.chips(cfg["chips"])
    dev = devices[0]
    from harness import reference, system, traffic
    from harness.corpus import Corpus

    controls = [cfg["control"]] + (["high"] if cfg["control"] == "int4"
                                   else [])
    lines = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        corpus = Corpus(cfg, seed)
        index, pruner = system.build(cfg, corpus, devices)
        Q = corpus.queries(mix["query_pool"], dev)
        server = system.serve(index, pruner, cfg["k"])
        t0 = time.perf_counter()
        log_ = traffic.LOOPS[mix["loop"]](server, Q, mix, t0, args.seconds,
                                          seed)
        server.close()
        del server, index, pruner
        gc.collect()
        arr = log_.arrays()
        Qs, scores, ids = run.sample(log_, arr, Q, seed)
        exact = reference.Exact(corpus, dev, Qs, cfg["k"])
        line = dict(seed=seed, answered=int(arr["ok"].sum()),
                    failed=int((~arr["ok"]).sum()),
                    sound=exact.read(scores, ids),
                    control={c: reference.control(exact, Qs, c)
                             for c in controls},
                    seconds=time.perf_counter() - t)
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(dict(workload=args.workload, **line))
                        + "\n")
        lines.append(line)
        del corpus, exact
        gc.collect()
    for key in ("score_err", "rank_gap"):
        summary = dict(number=key,
                       sound_max=max(x["sound"][key] for x in lines))
        for c in controls:
            summary[f"{c}_min"] = min(x["control"][c][key] for x in lines)
        print(json.dumps(summary), flush=True)
    return lines


if __name__ == "__main__":
    main()
