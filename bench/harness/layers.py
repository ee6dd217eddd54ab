"""Arithmetic shared by the per-layer metric readers.

``facts`` is what one traced run recorded: ``trace`` (``xtrace.reduce``
of the window), ``batches`` (the server's ``batch_log`` rows completed in
the window: size, dispatch time, completion time), ``seconds``,
``max_batch``, ``config``, ``peaks``, ``index_bytes`` (index bytes on the
busiest chip) and ``gen_lag_s`` (actual minus scheduled send, per
request).
"""
from __future__ import annotations

import numpy as np


def dispatch_s(facts: dict) -> float | None:
    """Device-busy seconds of the busiest chip per dispatch completed in
    the window."""
    if not facts["batches"] or not facts["trace"]["devices"]:
        return None
    busy = max(d["busy_ns"] for d in facts["trace"]["devices"]) / 1e9
    return busy / len(facts["batches"])


def idle_share(facts: dict) -> float | None:
    devs = facts["trace"]["devices"]
    if not devs:
        return None
    busy = np.mean([d["busy_ns"] for d in devs])
    return 100.0 * (1.0 - busy / facts["trace"]["window_ns"])


def scan_bound_s(facts: dict) -> float:
    """Least time one dispatch of ``max_batch`` queries could take on the
    busiest chip: the larger of its bytes over HBM bandwidth and its
    operations over the bf16 peak. Bytes: the chip's index rows, the
    projection and the queries; operations: the scores and the projection.
    """
    cfg, peaks, B = facts["config"], facts["peaks"], facts["max_batch"]
    d, width = cfg["d"], cfg.get("m") or cfg["d"]
    itemsize = 1 if cfg["dtype"] == "int8" else 4
    n_local = facts["index_bytes"] // (width * itemsize)
    pruned = cfg.get("m") is not None
    nbytes = facts["index_bytes"] + 4 * B * d + (4 * d * width if pruned
                                                 else 0)
    flops = 2 * B * n_local * width + (2 * B * d * width if pruned else 0)
    return max(nbytes / peaks["hbm_bytes_per_s"], flops / peaks["bf16_flops"])


def collective_s(facts: dict) -> float | None:
    devs = facts["trace"]["devices"]
    if not facts["batches"] or not any(d["collective_ns"] for d in devs):
        return None
    return np.mean([d["collective_ns"] for d in devs]) / 1e9 / len(
        facts["batches"])
