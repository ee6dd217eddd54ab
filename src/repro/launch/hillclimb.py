import os
os.environ["XLA_FLAGS"] = (os.environ.get("_REPRO_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""§Perf hillclimb driver — named variants for the three chosen cells.

Each variant is a (hypothesis, change) pair; the driver lowers+compiles it,
extracts the three roofline terms, and appends the result to
``experiments/hillclimb/<cell>.json`` so EXPERIMENTS.md §Perf can show the
full hypothesis → change → before → after → verdict log.

  PYTHONPATH=src python -m repro.launch.hillclimb --cell tt_retrieval
  PYTHONPATH=src python -m repro.launch.hillclimb --cell arctic_train
  PYTHONPATH=src python -m repro.launch.hillclimb --cell dlrm_train
"""
import argparse
import dataclasses
import json
import time

import numpy as np

PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9


def measure(bundle, mesh) -> dict:
    from repro.launch.flops import hlo_collectives, jaxpr_cost
    t0 = time.time()
    with mesh:
        compiled = bundle.lower().compile()
        acc = jaxpr_cost(bundle.fn, *bundle.args)
    hlo = compiled.as_text()
    coll = hlo_collectives(hlo)
    mem = compiled.memory_analysis()
    chips = int(np.prod(mesh.devices.shape))
    flops = acc["flops"]
    mem_bytes = bundle.meta.get("analytic_bytes", 0)
    t_compute = flops / (chips * PEAK_FLOPS)
    t_memory = mem_bytes / (chips * HBM_BW)
    t_coll = coll["total_bytes"] / LINK_BW
    terms = dict(compute=t_compute, memory=t_memory, collective=t_coll)
    dom = max(terms, key=terms.get)
    model_flops = bundle.meta.get("model_flops") or 0
    step = max(terms.values())
    return {
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll, "dominant": dom, "step_time_s": step,
        "roofline_fraction": (model_flops / step) / (chips * PEAK_FLOPS)
        if step else 0.0,
        "collective_bytes_dev": coll["total_bytes"],
        "temp_gib": getattr(mem, "temp_size_in_bytes", 0) / 2**30,
        "global_flops": flops, "model_flops": model_flops,
        "compile_s": round(time.time() - t0, 1),
    }


def tt_retrieval_variants():
    """Paper cell: 1 query vs 1M candidates. Dominant term = index stream."""
    from repro.configs.registry import get_arch
    spec = get_arch("two-tower-retrieval")
    cell = spec.cell("retrieval_cand")

    def variant(name, hypothesis, **dims):
        c = dataclasses.replace(cell, dims={**cell.dims, **dims})
        return name, hypothesis, spec, c

    return [
        variant("baseline_f32_256",
                "index stream C*256*4B dominates; collective (top-k merge) "
                "is ~200KB and secondary"),
        variant("pca50_f32_128",
                "PAPER: m=d/2 halves streamed bytes -> memory term /2; "
                "quality cost <5% nDCG (benchmarks Table 1)",
                index_dim=128),
        variant("pca50_int8_128",
                "BEYOND PAPER: int8 index on the rotated basis -> bytes /4 "
                "again (8x total); scale folds into q-hat so scan kernel "
                "is unchanged; expect collective term to become dominant",
                index_dim=128, int8=1),
        variant("pca75_int8_64",
                "BEYOND PAPER: 75% cutoff (paper: robust for low-rank "
                "encoders) + int8 -> 16x fewer bytes than baseline",
                index_dim=64, int8=1),
        variant("pca50_int8_hier_merge",
                "after compression the flat 256-shard top-k all-gather "
                "(205KB/dev) dominates: two-stage merge (model axis, then "
                "dp) cuts gather volume to (16+16)*k*8B = 25.6KB -> ~8x "
                "less collective",
                index_dim=128, int8=1, hier_merge=1),
        variant("pca75_int8_hier_merge",
                "compose 75% PCA + int8 + hierarchical merge: all three "
                "terms now within ~2x of each other (balanced design)",
                index_dim=64, int8=1, hier_merge=1),
    ]


def arctic_train_variants():
    """Most collective-bound cell: FSDP weight re-gathers x microbatches."""
    from repro.configs.registry import get_arch
    spec = get_arch("arctic-480b")
    cell = spec.cell("train_4k")

    def variant(name, hypothesis, **cfg_over):
        s = dataclasses.replace(spec, cfg=dataclasses.replace(spec.cfg,
                                                              **cfg_over))
        return name, hypothesis, s, cell

    return [
        variant("baseline_mb16",
                "FSDP gathers weights per layer per microbatch x3 passes "
                "(fwd/bwd/remat): collective ~ 16 mb x 35 L x ~1.6GB"),
        variant("mb8",
                "halve microbatches -> FSDP re-gather bytes /2; activation "
                "memory x2 (2->4 GiB, still under HBM)",
                microbatch=8),
        variant("mb4",
                "quarter microbatches -> collective /4 vs baseline; "
                "activations x4 — check HBM headroom",
                microbatch=4),
        variant("mb4_group1024",
                "larger MoE dispatch groups cut all-to-all count per layer "
                "(same bytes, fewer launches); dispatch transient x2",
                microbatch=4, moe_group_size=1024),
        variant("cf1.0",
                "mb count refuted as the lever (collective is mb-invariant "
                "=> dominated by EP-side expert_in gathers over the "
                "FSDP-sharded ff dim). Shrink the gathered buffer directly: "
                "capacity_factor 1.25 -> 1.0 cuts C=10 -> 8 per group",
                capacity_factor=1.0),
        variant("cf1.0_mb8",
                "compose the capacity cut with mb8 (mb8 still halves the "
                "activation-side TP all-reduces even if expert gathers are "
                "invariant)",
                capacity_factor=1.0, microbatch=8),
        variant("moe_dp_d_model",
                "STRUCTURAL: FSDP-shard expert d_model instead of ff. The "
                "expert GEMMs then contract/produce the dp-sharded dim, so "
                "cross-dp traffic becomes (E_loc,G_loc,C,ff) partial-sum "
                "psums + (…,d) gathers ≈ 15-20MB/layer/pass instead of "
                "gathering 294MB dispatched activations",
                capacity_factor=1.0, moe_dp_dim="d_model"),
        variant("moe_dp_d_model_mb8",
                "compose structural fix with mb8 to also halve the "
                "remaining activation-side TP collectives",
                capacity_factor=1.0, moe_dp_dim="d_model", microbatch=8),
    ]


def dlrm_train_variants():
    """Worst-fraction family cell: dense-optimizer traffic + lookup pattern."""
    from repro.configs.registry import get_arch
    spec = get_arch("dlrm-mlperf")
    cell = spec.cell("train_batch")
    out = [("baseline_adamw_dense",
            "dense AdamW on 24B table params: optimizer RW ~386GB/step "
            "dominates memory term; XLA gather/scatter on FSDP tables "
            "drives collective",
            spec, cell)]
    rw = dataclasses.replace(spec, optimizer="rowwise")
    out.append(("rowwise_sparse",
                "gather rows OUTSIDE autodiff + rowwise AdaGrad: dense "
                "table grads never exist; optimizer traffic O(B*F*E) "
                "-> memory term ~/100; table scatter in place (donated)",
                rw, cell))
    rw16 = dataclasses.replace(
        rw, cfg=dataclasses.replace(rw.cfg, param_dtype="bfloat16"))
    out.append(("rowwise_bf16_tables",
                "XLA's sharded-gather strategy replicates row outputs at "
                "global batch (26 x 832MiB); bf16 tables halve every row "
                "byte moved (industry-standard fp16/bf16 embeddings)",
                rw16, cell))
    return out


def smollm_train_variants():
    """Bonus cell: the over-parallelisation finding made concrete."""
    from repro.configs.registry import get_arch
    spec = get_arch("smollm-135m")
    cell = spec.cell("train_4k")

    def variant(name, hypothesis, **cfg_over):
        s = dataclasses.replace(spec, cfg=dataclasses.replace(spec.cfg,
                                                              **cfg_over))
        return name, hypothesis, s, cell

    return [
        variant("baseline_tp16_fsdp",
                "TP16 per-layer all-reduces x remat x microbatches cost "
                "~21x the compute for a 135M model"),
        variant("dp_only",
                "replicate params (540MB fp32 fits trivially), batch-only "
                "sharding: collective collapses to the one grad all-reduce "
                "(~0.5GB/dev) => compute-bound, ~20x faster step",
                parallelism="dp_only"),
        variant("dp_only_mb1",
                "microbatching exists only for memory; DP-only activations "
                "are tiny, so drop it and save the grad-accum passes",
                parallelism="dp_only", microbatch=1),
    ]


CELLS = {
    "tt_retrieval": tt_retrieval_variants,
    "arctic_train": arctic_train_variants,
    "dlrm_train": dlrm_train_variants,
    "smollm_train": smollm_train_variants,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True, choices=sorted(CELLS))
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--only", default=None, help="variant name filter")
    ap.add_argument("--out", default="experiments/hillclimb")
    args = ap.parse_args()

    from repro.configs.steps import BUNDLE_BUILDERS
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=(args.mesh == "multipod"))
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, f"{args.cell}_{args.mesh}.json")
    log = []
    if os.path.exists(path):
        with open(path) as f:
            log = json.load(f)
    done = {e["variant"] for e in log}

    for name, hypothesis, spec, cell in CELLS[args.cell]():
        if args.only and args.only != name:
            continue
        if name in done:
            print(f"skip {name} (already measured)")
            continue
        print(f"== {name}: {hypothesis[:70]}")
        try:
            bundle = BUNDLE_BUILDERS[spec.family](spec, cell, mesh)
            m = measure(bundle, mesh)
            m.update(variant=name, hypothesis=hypothesis, status="ok")
        except Exception as e:
            import traceback
            m = dict(variant=name, hypothesis=hypothesis, status="error",
                     error=f"{type(e).__name__}: {e}",
                     traceback=traceback.format_exc()[-2000:])
        log.append(m)
        with open(path, "w") as f:
            json.dump(log, f, indent=1)
        if m["status"] == "ok":
            print(f"   compute={m['t_compute_s']:.3e}s memory={m['t_memory_s']:.3e}s "
                  f"collective={m['t_collective_s']:.3e}s dom={m['dominant']} "
                  f"step={m['step_time_s']:.3e}s temp={m['temp_gib']:.1f}GiB")
        else:
            print(f"   ERROR {m['error'][:120]}")


if __name__ == "__main__":
    main()
