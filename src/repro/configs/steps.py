"""Step bundles: (fn, abstract args, shardings) per (arch × shape × mesh).

A ``StepBundle`` is everything the dry-run / launcher needs to AOT-compile
one cell: the step function, ``ShapeDtypeStruct`` stand-ins for every input
(no allocation), and NamedSharding trees resolved from the arch's sharding
rules against the given mesh. ``bundle.lower()`` is the single entry point
``launch/dryrun.py`` drives.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchSpec, ShapeCell, round_up
from repro.models import biencoder as BE, gnn as G, recsys as R, transformer as T
from repro.optim import adafactor, adamw
from repro.par import compat, sharding as SH

TOPK_SERVE = 100  # retrieval top-k


def sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(tuple(int(x) for x in shape), jnp.dtype(dtype))


@dataclasses.dataclass
class StepBundle:
    name: str
    fn: Callable
    args: tuple
    in_specs: tuple
    out_specs: Any
    mesh: Mesh
    donate: tuple = ()
    meta: dict = dataclasses.field(default_factory=dict)

    def _ns(self, tree):
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), tree,
            is_leaf=lambda x: isinstance(x, P))

    def jitted(self):
        kw = {}
        if self.in_specs is not None:
            kw["in_shardings"] = tuple(self._ns(s) for s in self.in_specs)
        if self.out_specs is not None:
            kw["out_shardings"] = self._ns(self.out_specs)
        if self.donate:
            kw["donate_argnums"] = self.donate
        return jax.jit(self.fn, **kw)

    def lower(self):
        return self.jitted().lower(*self.args)


# ---------------------------------------------------------------------------
# shared optimizer plumbing
# ---------------------------------------------------------------------------


def rowwise_opt_init(params):
    """Rowwise-AdaGrad tables + AdamW rest (see repro.optim.rowwise)."""
    rest = {k: v for k, v in params.items() if k != "tables"}
    return {"adamw": adamw.adamw_init(rest),
            "acc": [jnp.zeros((t.shape[0],), jnp.float32)
                    for t in params["tables"]]}


def _opt_pack(optimizer: str):
    if optimizer == "adafactor":
        return adafactor.adafactor_init, adafactor.adafactor_update
    if optimizer == "rowwise":
        return rowwise_opt_init, None   # update lives in the rowwise bundle
    return adamw.adamw_init, adamw.adamw_update


def _zero1_like(opt_sds: Any, base_specs: Any, params_sds: Any, mesh: Mesh,
                optimizer: str) -> Any:
    if optimizer == "adamw":
        return adamw.opt_state_specs(base_specs, params_sds, mesh, zero1=True)
    # adafactor: factored leaves don't mirror param structure — dp-shard the
    # first divisible dim of each state leaf (ZeRO-1 flavoured)
    dp = SH.logical_to_physical("dp", mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))

    def leaf_spec(leaf):
        for d, n in enumerate(leaf.shape):
            if n % dp_size == 0 and n > 1:
                parts = [None] * len(leaf.shape)
                parts[d] = dp if len(dp) > 1 else dp[0]
                return P(*parts)
        return P()

    return {"v": jax.tree.map(leaf_spec, opt_sds["v"]), "step": P()}


def _make_train_step(loss_fn: Callable, optimizer: str, lr: float = 1e-4,
                     microbatch: int = 1, accum_dtype=jnp.float32,
                     mb_shardings=None):
    """Train step with gradient-accumulation microbatching.

    ``microbatch`` K splits the global batch into K sequential microbatches
    inside a lax.scan: activation memory drops by K (the difference between
    a 480B model fitting a pod or not); grads accumulate in ``accum_dtype``
    (bf16 for the largest models — halves grad-buffer HBM at ~1e-3 relative
    accumulation error over K<=32 microbatches).

    ``mb_shardings``: NamedSharding tree pinning the reshaped (K, B/K, ...)
    batch to keep B/K on the dp axes — without the constraint GSPMD is free
    to shard the K dim instead, silently un-sharding every activation.
    """
    opt_init, opt_update = _opt_pack(optimizer)

    def step(params, opt_state, batch):
        if microbatch <= 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        else:
            mbs = jax.tree.map(
                lambda x: x.reshape(microbatch, x.shape[0] // microbatch,
                                    *x.shape[1:]), batch)
            if mb_shardings is not None:
                mbs = jax.tree.map(jax.lax.with_sharding_constraint, mbs,
                                   mb_shardings)
            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, accum_dtype),
                              params)

            def mb_step(carry, mb):
                acc_l, acc_g = carry
                l, g = jax.value_and_grad(loss_fn)(params, mb)
                acc_g = jax.tree.map(
                    lambda a, b: a + b.astype(accum_dtype), acc_g, g)
                return (acc_l + l, acc_g), None

            (loss, gsum), _ = jax.lax.scan(
                mb_step, (jnp.float32(0.0), g0), mbs)
            loss = loss / microbatch
            grads = jax.tree.map(lambda g: (g / microbatch), gsum)
        new_params, new_opt = opt_update(grads, opt_state, params,
                                         jnp.float32(lr))
        return new_params, new_opt, {"loss": loss}

    return step, opt_init


def _microbatch_of(cfg) -> tuple[int, Any]:
    mb = getattr(cfg, "microbatch", 1) or 1
    dt = jnp.dtype(getattr(cfg, "grad_accum_dtype", "float32"))
    return mb, dt


def _train_bundle(name, mesh, params_sds, param_spec, batch_sds, batch_spec,
                  loss_fn, optimizer, meta, microbatch: int = 1,
                  accum_dtype=jnp.float32) -> StepBundle:
    mb_ns = None
    if microbatch > 1:
        mb_ns = jax.tree.map(
            lambda s: NamedSharding(mesh, P(None, *s)), batch_spec,
            is_leaf=lambda x: isinstance(x, P))
    step, opt_init = _make_train_step(loss_fn, optimizer,
                                      microbatch=microbatch,
                                      accum_dtype=accum_dtype,
                                      mb_shardings=mb_ns)
    opt_sds = jax.eval_shape(opt_init, params_sds)
    opt_spec = _zero1_like(opt_sds, param_spec, params_sds, mesh, optimizer)
    return StepBundle(
        name=name, fn=step, mesh=mesh,
        args=(params_sds, opt_sds, batch_sds),
        in_specs=(param_spec, opt_spec, batch_spec),
        out_specs=(param_spec, opt_spec, {"loss": P()}),
        donate=(0, 1),
        meta=meta)


# ---------------------------------------------------------------------------
# LM family
# ---------------------------------------------------------------------------


def _lm_param_sds(cfg: T.TransformerConfig, serve: bool):
    c = dataclasses.replace(cfg, param_dtype="bfloat16") if serve else cfg
    return jax.eval_shape(lambda: T.init_lm(jax.random.PRNGKey(0), c)), c


def _dp(mesh: Mesh):
    dp = SH.logical_to_physical("dp", mesh)
    return dp if len(dp) > 1 else dp[0]


def _lm_mem_bytes(cfg: T.TransformerConfig, kind: str, B: int, S: int) -> int:
    """Analytic global HBM traffic per step (napkin model, documented in
    EXPERIMENTS.md §Roofline). Attention interiors are assumed VMEM-resident
    (flash kernel on the TPU target)."""
    P = cfg.param_count()
    Pa = cfg.active_param_count()
    L, d, V = cfg.n_layers, cfg.d_model, cfg.vocab
    kv = cfg.n_kv_heads * cfg.hd
    tokens = B * S
    if kind == "train":
        params = 3 * P * 2 + 2 * P * 4 + 4 * P * 4 + P * 4  # casts+grads+adam
        acts = L * tokens * d * 2 * 20          # fwd+bwd+remat tensor passes
        logits = 2 * 2 * tokens * V * 4 / max(1, S // 2048)  # chunked, fwd+bwd
        return int(params + acts + logits)
    if kind == "prefill":
        return int(P * 2 + L * tokens * d * 2 * 6 + 2 * L * tokens * kv * 2)
    if kind == "decode":
        cache = 2 * L * B * S * kv * 2
        return int(Pa * 2 + cache + B * V * 4)
    # decode_long: rolling window cache
    W = cfg.sliding_window or S
    return int(Pa * 2 + 2 * L * B * W * kv * 2 + B * V * 4)


def lm_bundle(spec_: ArchSpec, cell: ShapeCell, mesh: Mesh) -> StepBundle:
    cfg: T.TransformerConfig = spec_.cfg
    rules = (SH.lm_rules_dp_only() if cfg.parallelism == "dp_only"
             else SH.lm_rules(moe=cfg.n_experts > 0, moe_dp_dim=cfg.moe_dp_dim))
    S, B = cell.dims["seq_len"], cell.dims["global_batch"]
    dp = _dp(mesh)
    tokens_B = B
    meta = dict(family="lm", arch=spec_.arch_id, shape=cell.name,
                params=cfg.param_count(), active_params=cfg.active_param_count(),
                dims=dict(cell.dims), n_layers=cfg.n_layers, d_model=cfg.d_model,
                vocab=cfg.vocab,
                analytic_bytes=_lm_mem_bytes(cfg, cell.kind, B, S))

    act_ns = NamedSharding(mesh, P(dp, None, None)) if B > 1 else None

    if cell.kind == "train":
        params_sds, cfg_t = _lm_param_sds(cfg, serve=False)
        cfg_t = dataclasses.replace(cfg_t, act_sharding=act_ns)
        pspec = SH.param_specs(params_sds, mesh, rules)
        batch_sds = {"tokens": sds((B, S), jnp.int32),
                     "labels": sds((B, S), jnp.int32)}
        bspec = {"tokens": P(dp, None), "labels": P(dp, None)}
        logit_ns = NamedSharding(mesh, P(dp, None, "model"))
        loss = partial(_lm_loss, cfg=cfg_t, logit_sharding=logit_ns)
        meta["model_flops"] = 6 * cfg.active_param_count() * B * S
        meta["tokens"] = B * S
        mb, adt = _microbatch_of(cfg)
        meta["microbatch"] = mb
        return _train_bundle(f"{spec_.arch_id}:{cell.name}", mesh, params_sds,
                             pspec, batch_sds, bspec, loss, spec_.optimizer,
                             meta, microbatch=mb, accum_dtype=adt)

    params_sds, cfg_s = _lm_param_sds(cfg, serve=True)
    cfg_s = dataclasses.replace(cfg_s, act_sharding=act_ns)
    pspec = SH.param_specs(params_sds, mesh, rules)
    hd = cfg.hd
    meta["model_flops"] = 2 * cfg.active_param_count() * B * (
        S if cell.kind == "prefill" else 1)

    if cell.kind == "prefill":
        def fn(params, tokens):
            return T.prefill(params, tokens, cfg_s)
        cache_spec = P(None, dp, "model", None, None)  # seq-sharded KV
        return StepBundle(
            name=f"{spec_.arch_id}:{cell.name}", fn=fn, mesh=mesh,
            args=(params_sds, sds((B, S), jnp.int32)),
            in_specs=(pspec, P(dp, None)),
            out_specs=(P(dp, None), (cache_spec, cache_spec)),
            meta=meta)

    if cell.kind == "decode":
        cache_sds = sds((cfg.n_layers, B, S, cfg.n_kv_heads, hd), jnp.bfloat16)
        cache_spec = P(None, dp, "model", None, None)

        def fn(params, kv_cache, token, pos):
            return T.decode_step(params, kv_cache, token, pos, cfg_s)

        return StepBundle(
            name=f"{spec_.arch_id}:{cell.name}", fn=fn, mesh=mesh,
            args=(params_sds, (cache_sds, cache_sds),
                  sds((B,), jnp.int32), sds((), jnp.int32)),
            in_specs=(pspec, (cache_spec, cache_spec), P(dp), P()),
            out_specs=(P(dp, None), (cache_spec, cache_spec)),
            donate=(1,),
            meta=meta)

    if cell.kind == "decode_long":
        # sliding-window rolling buffer: live cache = window, not seq_len
        W = cfg.sliding_window
        assert W is not None, "long_500k requires a sub-quadratic arch"
        cache_sds = sds((cfg.n_layers, B, W, cfg.n_kv_heads, hd), jnp.bfloat16)
        cache_spec = P(None, None, "model", None, None)  # B=1: shard window

        def fn(params, kv_cache, token, pos):
            return T.decode_step_sliding(params, kv_cache, token, pos, cfg_s)

        meta["window"] = W
        return StepBundle(
            name=f"{spec_.arch_id}:{cell.name}", fn=fn, mesh=mesh,
            args=(params_sds, (cache_sds, cache_sds),
                  sds((B,), jnp.int32), sds((), jnp.int32)),
            in_specs=(pspec, (cache_spec, cache_spec), P(), P()),
            out_specs=(P(None, None), (cache_spec, cache_spec)),
            donate=(1,),
            meta=meta)

    raise ValueError(f"unknown LM cell kind {cell.kind}")


def _lm_loss(params, batch, cfg, logit_sharding=None):
    return T.forward_train(params, batch["tokens"], batch["labels"], cfg,
                           logit_sharding=logit_sharding)


# ---------------------------------------------------------------------------
# GNN family
# ---------------------------------------------------------------------------


def gnn_bundle(spec_: ArchSpec, cell: ShapeCell, mesh: Mesh) -> StepBundle:
    cfg: G.GNNConfig = spec_.cfg
    d = cell.dims
    ndev = int(np.prod(mesh.devices.shape))
    all_axes = tuple(mesh.axis_names)

    if cell.kind == "train_sampled":
        # static padded subgraph from the CSR fanout sampler
        bn, f0, f1 = d["batch_nodes"], d["fanout0"], d["fanout1"]
        N = bn + bn * f0 + bn * f0 * f1
        E = bn * f0 + bn * f0 * f1
        d_feat = d["d_feat"]
    elif cell.name == "molecule":
        N = d["batch"] * d["n_nodes"]
        E = d["batch"] * d["n_edges"]
        d_feat = d["d_feat"]
    else:
        N, E, d_feat = d["n_nodes"], d["n_edges"], d["d_feat"]

    big = E >= 1_000_000
    E_pad = round_up(E, 512) if big else E
    cfg_r = dataclasses.replace(cfg, d_in=d_feat)

    params_sds = jax.eval_shape(lambda: G.init_gnn(jax.random.PRNGKey(0), cfg_r))
    pspec = SH.param_specs(params_sds, mesh, SH.gnn_rules())

    batch_sds = {
        "nodes": sds((N, d_feat), jnp.float32),
        "edges": sds((E_pad, cfg.d_edge_in), jnp.float32),
        "edge_index": sds((2, E_pad), jnp.int32),
        "edge_mask": sds((E_pad,), jnp.float32),
        "targets": sds((N, cfg.d_out), jnp.float32),
        "node_mask": sds((N,), jnp.float32),
    }
    # big graphs: edges shard over every axis (pure data); node tables
    # replicate. Small graphs (< 1M edges, not shard-even) replicate fully —
    # there is no data to parallelise and the dry-run records that honestly.
    if big:
        bspec = {"nodes": P(), "edges": P(all_axes, None),
                 "edge_index": P(None, all_axes), "edge_mask": P(all_axes),
                 "targets": P(), "node_mask": P()}
    else:
        bspec = {k: P() if v.ndim == 1 else P(*([None] * v.ndim))
                 for k, v in batch_sds.items()}

    loss = partial(_gnn_loss, cfg=cfg_r)
    h = cfg.d_hidden
    fwd_flops = 2 * (E * (4 * h * h) + N * (3 * h * h)) * cfg.n_layers \
        + 2 * N * (d_feat * h + h * h) + 2 * E_pad * (cfg.d_edge_in * h + h * h) \
        + 2 * N * (h * h + h * cfg.d_out)
    # traffic: per layer, gather 2 endpoint features + write messages +
    # scatter-add, fwd+bwd+remat (~3x); params negligible
    mem = 3 * cfg.n_layers * (3 * E * h * 4 + 4 * N * h * 4) \
        + 3 * N * (d_feat + cfg.d_out) * 4
    meta = dict(family="gnn", arch=spec_.arch_id, shape=cell.name,
                params=cfg_r.param_count(), active_params=cfg_r.param_count(),
                model_flops=3 * fwd_flops,  # fwd + bwd(2x)
                n_nodes=N, n_edges=E_pad, d_hidden=h,
                dims=dict(cell.dims), analytic_bytes=int(mem))
    return _train_bundle(f"{spec_.arch_id}:{cell.name}", mesh, params_sds,
                         pspec, batch_sds, bspec, loss, spec_.optimizer, meta)


def _gnn_loss(params, batch, cfg):
    return G.mse_loss(params, batch, cfg)


# ---------------------------------------------------------------------------
# RecSys family
# ---------------------------------------------------------------------------


def _recsys_mem_bytes(cfg: R.RecsysConfig, kind: str, B: int, C: int = 0) -> int:
    """Analytic global HBM traffic. NOTE the dense-optimizer reality: AdamW
    moments for the full embedding tables are read+written every step —
    the dominant term for DLRM-scale tables (a designed-in hillclimb
    target: rowwise/sparse optimizers)."""
    e = cfg.embed_dim
    if cfg.kind == "two_tower":
        table_p = (cfg.user_vocab + cfg.item_vocab) * e
        dims = (e,) + cfg.tower_mlp
        mlp_p = 2 * sum(dims[i] * dims[i + 1] for i in range(len(dims) - 1))
        if kind == "train":
            return int(3 * 2 * B * e * 4 + 6 * table_p * 4 + 7 * mlp_p * 4
                       + 3 * B * B * 4)
        if kind == "serve":
            return int(2 * B * e * 4 + mlp_p * 4 + 3 * B * sum(dims) * 4)
        return int(C * cfg.tower_mlp[-1] * 4 + mlp_p * 4 + e * 4)
    F = cfg.n_sparse
    table_p = sum(cfg.vocab_sizes) * e
    mlp_p = cfg.param_count() - table_p
    act_w = F * e + (sum(cfg.bot_mlp) + sum(cfg.top_mlp)
                     + sum(cfg.deep_mlp) + cfg.n_attn_layers
                     * cfg.n_heads * cfg.d_attn * F)
    if kind == "train":
        return int(3 * B * F * e * 4 + 6 * table_p * 4 + 7 * mlp_p * 4
                   + 3 * B * act_w * 4)
    if kind == "serve":
        return int(B * F * e * 4 + mlp_p * 4 + B * act_w * 4)
    f_item = F - F // 2
    return int(C * f_item * e * 4 + mlp_p * 4 + C * act_w * 4)


def recsys_bundle(spec_: ArchSpec, cell: ShapeCell, mesh: Mesh) -> StepBundle:
    cfg: R.RecsysConfig = spec_.cfg
    rules = SH.recsys_rules()
    dp = _dp(mesh)
    all_axes = tuple(mesh.axis_names)
    params_sds = jax.eval_shape(lambda: R.init_recsys(jax.random.PRNGKey(0), cfg))
    pspec = SH.param_specs(params_sds, mesh, rules)
    B = cell.dims["batch"]
    C0 = round_up(cell.dims.get("n_candidates", 0), 512)
    meta = dict(family="recsys", arch=spec_.arch_id, shape=cell.name,
                params=cfg.param_count(), active_params=_recsys_active(cfg),
                model_flops=None, dims=dict(cell.dims),
                analytic_bytes=_recsys_mem_bytes(cfg, cell.kind, B, C0))

    if cfg.kind == "two_tower":
        return _two_tower_bundle(spec_, cell, mesh, cfg, params_sds, pspec, meta)

    F = cfg.n_sparse
    batch_sds = {"sparse": sds((B, F), jnp.int32),
                 "label": sds((B,), jnp.float32)}
    bspec = {"sparse": P(dp, None), "label": P(dp)}
    if cfg.kind == "dlrm":
        batch_sds["dense"] = sds((B, cfg.n_dense), jnp.float32)
        bspec["dense"] = P(dp, None)

    per_sample = _ctr_flops_per_sample(cfg)
    if cell.kind == "train":
        meta["model_flops"] = 3 * per_sample * B
        if spec_.optimizer == "rowwise":
            # sparse-grad table path: optimizer traffic O(batch·dim), see
            # repro.optim.rowwise. Analytic bytes shrink accordingly.
            e = cfg.embed_dim
            meta["analytic_bytes"] = int(
                6 * B * cfg.n_sparse * e * 4      # gather + grad + scatter
                + 7 * (meta["params"] - sum(cfg.vocab_sizes) * e) * 4
                + 3 * B * 4096)
            return _recsys_rowwise_bundle(spec_, cell, mesh, cfg, params_sds,
                                          pspec, batch_sds, bspec, meta)
        loss = partial(_ctr_loss, cfg=cfg)
        return _train_bundle(f"{spec_.arch_id}:{cell.name}", mesh, params_sds,
                             pspec, batch_sds, bspec, loss, spec_.optimizer, meta)

    if cell.kind == "serve":
        meta["model_flops"] = per_sample * B

        def fn(params, batch):
            return R.forward_ctr(params, batch, cfg)

        return StepBundle(
            name=f"{spec_.arch_id}:{cell.name}", fn=fn, mesh=mesh,
            args=(params_sds, batch_sds),
            in_specs=(pspec, bspec), out_specs=P(dp),
            meta=meta)

    # retrieval: 1 user context vs C candidate items
    C = round_up(cell.dims["n_candidates"], 512)
    f_user, f_item = R.ctr_user_item_split(cfg)
    user_sds = {"sparse": sds((1, f_user), jnp.int32)}
    uspec = {"sparse": P()}
    if cfg.kind == "dlrm":
        user_sds["dense"] = sds((1, cfg.n_dense), jnp.float32)
        uspec["dense"] = P()
    cand_sds = sds((C, f_item), jnp.int32)
    meta["model_flops"] = per_sample * C
    meta["n_candidates"] = C

    def fn(params, user_batch, cand_sparse):
        scores = R.ctr_retrieval_scores(params, user_batch, cand_sparse, cfg)
        return _sharded_topk_1d(scores, TOPK_SERVE, mesh)

    return StepBundle(
        name=f"{spec_.arch_id}:{cell.name}", fn=fn, mesh=mesh,
        args=(params_sds, user_sds, cand_sds),
        in_specs=(pspec, uspec, P(all_axes, None)),
        out_specs=(P(), P()),
        meta=meta)


def _ctr_loss(params, batch, cfg):
    return R.bce_loss(params, batch, cfg)


def _recsys_rowwise_bundle(spec_, cell, mesh, cfg, params_sds, pspec,
                           batch_sds, bspec, meta) -> StepBundle:
    """CTR train step with rows gathered OUTSIDE autodiff + rowwise AdaGrad.

    Dense table grads never exist; tables are donated so the row updates
    scatter in place. Dense (non-table) params keep AdamW.
    """
    from repro.optim import rowwise as RW

    def bce_from_logit(logit, y):
        return jnp.mean(jnp.maximum(logit, 0) - logit * y
                        + jnp.log1p(jnp.exp(-jnp.abs(logit))))

    opt_init = rowwise_opt_init
    dp = _dp(mesh)
    rows_ns = NamedSharding(mesh, P(dp, None))

    def step(params, opt_state, batch):
        tables = params["tables"]
        rest = {k: v for k, v in params.items() if k != "tables"}
        idx = batch["sparse"]                                   # (B, F)
        # gather OUTSIDE autodiff; pin rows batch-sharded — without the
        # constraint XLA materialises each table's rows at GLOBAL batch
        # (26 x 832 MiB all-gathers on this cell)
        rows = [jax.lax.with_sharding_constraint(
                    jnp.take(t, idx[:, f], axis=0), rows_ns)
                for f, t in enumerate(tables)]

        def loss_fn(rest_, rows_):
            emb = jnp.stack(rows_, axis=1).astype(jnp.float32)
            logit = R.forward_ctr_from_emb(rest_, emb, batch, cfg)
            return bce_from_logit(logit, batch["label"].astype(jnp.float32))

        loss, (g_rest, g_rows) = jax.value_and_grad(
            loss_fn, argnums=(0, 1))(rest, rows)
        lr = jnp.float32(1e-4)
        new_rest, new_adam = adamw.adamw_update(g_rest, opt_state["adamw"],
                                                rest, lr)
        new_tables, new_acc = [], []
        for f, (t, a, gr) in enumerate(zip(tables, opt_state["acc"], g_rows)):
            nt, na = RW.rowwise_adagrad_update(t, a, idx[:, f], gr, lr)
            new_tables.append(nt)
            new_acc.append(na)
        new_params = dict(new_rest, tables=new_tables)
        return new_params, {"adamw": new_adam, "acc": new_acc}, {"loss": loss}

    opt_sds = jax.eval_shape(opt_init, params_sds)
    rest_spec = {k: v for k, v in pspec.items() if k != "tables"}
    acc_spec = [P(s[0]) for s in pspec["tables"]]   # rows spec of each table
    opt_spec = {"adamw": adamw.opt_state_specs(rest_spec,
                                               {k: v for k, v in params_sds.items()
                                                if k != "tables"}, mesh),
                "acc": acc_spec}
    meta["optimizer"] = "rowwise-adagrad"
    return StepBundle(
        name=f"{spec_.arch_id}:{cell.name}", fn=step, mesh=mesh,
        args=(params_sds, opt_sds, batch_sds),
        in_specs=(pspec, opt_spec, bspec),
        out_specs=(pspec, opt_spec, {"loss": P()}),
        donate=(0, 1),
        meta=meta)


def _recsys_active(cfg: R.RecsysConfig) -> int:
    """Params actually touched per sample (few embedding rows, all MLPs)."""
    e = cfg.embed_dim
    emb_rows = (cfg.n_sparse if cfg.kind != "two_tower" else 2) * e
    total = cfg.param_count()
    table_rows = (sum(cfg.vocab_sizes) * e if cfg.kind != "two_tower"
                  else (cfg.user_vocab + cfg.item_vocab) * e)
    return total - table_rows + emb_rows


def _ctr_flops_per_sample(cfg: R.RecsysConfig) -> int:
    e = cfg.embed_dim
    F = cfg.n_sparse
    if cfg.kind == "dlrm":
        dims = (cfg.n_dense,) + cfg.bot_mlp
        bot = sum(2 * dims[i] * dims[i + 1] for i in range(len(dims) - 1))
        f = F + 1
        inter = 2 * f * f * e
        d_int = f * (f - 1) // 2 + cfg.bot_mlp[-1]
        dims = (d_int,) + cfg.top_mlp
        top = sum(2 * dims[i] * dims[i + 1] for i in range(len(dims) - 1))
        return bot + inter + top
    if cfg.kind == "deepfm":
        dims = (F * e,) + cfg.deep_mlp + (1,)
        deep = sum(2 * dims[i] * dims[i + 1] for i in range(len(dims) - 1))
        return deep + 4 * F * e
    if cfg.kind == "autoint":
        d_l = [e] + [cfg.n_heads * cfg.d_attn] * cfg.n_attn_layers
        fl = 0
        for i in range(cfg.n_attn_layers):
            fl += 2 * F * d_l[i] * (4 * d_l[i + 1]) + 2 * F * F * d_l[i + 1] * 2
        return fl + 2 * F * d_l[-1]
    dims = (e,) + cfg.tower_mlp
    return 2 * sum(2 * dims[i] * dims[i + 1] for i in range(len(dims) - 1))


def _two_tower_bundle(spec_, cell, mesh, cfg, params_sds, pspec, meta):
    dp = _dp(mesh)
    all_axes = tuple(mesh.axis_names)
    B = cell.dims["batch"]
    per_sample = _ctr_flops_per_sample(cfg)

    if cell.kind == "train":
        batch_sds = {"user_ids": sds((B,), jnp.int32),
                     "item_ids": sds((B,), jnp.int32),
                     "item_logq": sds((B,), jnp.float32)}
        bspec = {"user_ids": P(dp), "item_ids": P(dp), "item_logq": P(dp)}
        logit_sharding = NamedSharding(mesh, P(dp, "model"))
        loss = partial(_tt_loss, cfg=cfg, logit_sharding=logit_sharding)
        meta["model_flops"] = 3 * (per_sample * B + 2 * B * B * cfg.tower_mlp[-1])
        return _train_bundle(f"{spec_.arch_id}:{cell.name}", mesh, params_sds,
                             pspec, batch_sds, bspec, loss, spec_.optimizer, meta)

    if cell.kind == "serve":
        batch_sds = {"user_ids": sds((B,), jnp.int32),
                     "item_ids": sds((B,), jnp.int32)}
        bspec = {"user_ids": P(dp), "item_ids": P(dp)}
        meta["model_flops"] = per_sample * B

        def fn(params, batch):
            u = R.user_embedding(params, batch["user_ids"])
            v = R.item_embedding(params, batch["item_ids"])
            return (u * v).sum(-1)

        return StepBundle(
            name=f"{spec_.arch_id}:{cell.name}", fn=fn, mesh=mesh,
            args=(params_sds, batch_sds),
            in_specs=(pspec, bspec), out_specs=P(dp), meta=meta)

    # retrieval_cand: THE paper cell — user query vs precomputed item index.
    # dims overrides (hillclimb variants): index_dim = m after PCA pruning,
    # int8 = quantised index (+ per-dim scale folded into the query).
    C = round_up(cell.dims["n_candidates"], 512)
    d_full = cfg.tower_mlp[-1]
    m = int(cell.dims.get("index_dim", d_full))
    int8 = bool(cell.dims.get("int8", 0))
    index_sds = sds((C, m), jnp.int8 if int8 else jnp.float32)
    meta["model_flops"] = per_sample // 2 + 2 * C * m + 2 * d_full * m
    meta["n_candidates"] = C
    meta["index_dim"] = m
    meta["index_int8"] = int8
    meta["analytic_bytes"] = int(C * m * (1 if int8 else 4)
                                 + 2 * cfg.param_count() // 1000)

    hier = bool(cell.dims.get("hier_merge", 0))
    if m == d_full and not int8:
        def fn(params, item_index, user_ids):
            u = R.user_embedding(params, user_ids)           # (1, d)
            return _sharded_index_topk(item_index, u, TOPK_SERVE, mesh,
                                       hierarchical=hier)

        args = (params_sds, index_sds, sds((1,), jnp.int32))
        in_specs = (pspec, P(all_axes, None), P())
    else:
        # PCA-pruned (optionally int8) index: q̂ = (q @ W_m) ⊙ scale — the
        # same fused projection+fold the serving hot path traces
        # (repro.core.index.project_queries, one jit with the scan)
        W_sds = sds((d_full, m), jnp.float32)
        scale_sds = sds((m,), jnp.float32)

        def fn(params, item_index, W_m, scale, user_ids):
            from repro.core.index import project_queries
            u = R.user_embedding(params, user_ids)           # (1, d)
            q = project_queries(u, W_m, scale=scale)         # O(dm) transform
            return _sharded_index_topk(item_index, q, TOPK_SERVE, mesh,
                                       hierarchical=hier)

        args = (params_sds, index_sds, W_sds, scale_sds, sds((1,), jnp.int32))
        in_specs = (pspec, P(all_axes, None), P(), P(), P())

    return StepBundle(
        name=f"{spec_.arch_id}:{cell.name}", fn=fn, mesh=mesh,
        args=args, in_specs=in_specs,
        out_specs=(P(), P()),
        meta=meta)


def _tt_loss(params, batch, cfg, logit_sharding):
    return R.two_tower_loss(params, batch, cfg, logit_sharding=logit_sharding)


# ---------------------------------------------------------------------------
# BiEncoder family (the paper's own model — examples/launcher, not a cell)
# ---------------------------------------------------------------------------


def biencoder_bundle(spec_: ArchSpec, cell: ShapeCell, mesh: Mesh) -> StepBundle:
    cfg: BE.BiEncoderConfig = spec_.cfg
    rules = SH.biencoder_rules()
    dp = _dp(mesh)
    S, B = cell.dims["seq_len"], cell.dims["global_batch"]
    params_sds = jax.eval_shape(lambda: BE.init_biencoder(jax.random.PRNGKey(0), cfg))
    pspec = SH.param_specs(params_sds, mesh, rules)
    P = cfg.param_count()
    tok = 2 * B * S
    mem = (3 * P * 2 + 11 * P * 4 + cfg.n_layers * tok * cfg.d_model * 2 * 20
           if cell.kind == "train" else
           P * 2 + cfg.n_layers * B * S * cfg.d_model * 2 * 6)
    meta = dict(family="biencoder", arch=spec_.arch_id, shape=cell.name,
                params=P, active_params=P, dims=dict(cell.dims),
                analytic_bytes=int(mem))

    if cell.kind == "train":
        batch_sds = {k: sds((B, S), jnp.int32)
                     for k in ("q_tokens", "q_mask", "d_tokens", "d_mask")}
        bspec = {k: P(dp, None) for k in batch_sds}
        loss = partial(_be_loss, cfg=cfg)
        meta["model_flops"] = 6 * cfg.param_count() * 2 * B * S
        return _train_bundle(f"{spec_.arch_id}:{cell.name}", mesh, params_sds,
                             pspec, batch_sds, bspec, loss, spec_.optimizer, meta)

    def fn(params, tokens, mask):
        return BE.encode(params, tokens, mask, cfg)

    meta["model_flops"] = 2 * cfg.param_count() * B * S
    return StepBundle(
        name=f"{spec_.arch_id}:{cell.name}", fn=fn, mesh=mesh,
        args=(params_sds, sds((B, S), jnp.int32), sds((B, S), jnp.int32)),
        in_specs=(pspec, P(dp, None), P(dp, None)),
        out_specs=P(dp, None), meta=meta)


def _be_loss(params, batch, cfg):
    return BE.contrastive_loss(params, batch, cfg)


# ---------------------------------------------------------------------------
# Sharded top-k helpers (retrieval serving across the whole mesh)
# ---------------------------------------------------------------------------


def _sharded_index_topk(index: jax.Array, q: jax.Array, k: int, mesh: Mesh,
                        hierarchical: bool = False):
    """Exact top-k of q @ index^T with index rows sharded over every axis.

    ``hierarchical=True`` merges in two stages (within 'model', then across
    the dp axes): per-device gather volume drops from |devices|·k to
    (|model| + |dp|)·k — 8x on a 16x16 pod. Exactness and tie-breaks are
    preserved (see ``repro.core.index._staged_topk_merge``, which is the
    same machinery ``ShardedDenseIndex.search(merge=...)`` serves through).
    """
    from repro.core.index import _scan_topk, _staged_topk_merge
    axes = tuple(mesh.axis_names)
    ndev = int(np.prod(mesh.devices.shape))
    rows_per = index.shape[0] // ndev
    if hierarchical and len(axes) > 1:
        inner = ("model",) if "model" in axes else (axes[-1],)
        stages = (inner, tuple(a for a in axes if a not in inner))
    else:
        stages = (axes,)

    def shard_fn(idx_local, q_rep):
        pos = compat.axis_index(axes)
        s, ids = _scan_topk(idx_local, q_rep, k, vma_axes=axes)
        ids = jnp.where(ids >= 0, ids + pos * rows_per, -1)
        return _staged_topk_merge(s, ids, k, stages)

    # the merged top-k is replicated by construction (all_gather + same
    # reduction everywhere) but that can't be statically proven: check_vma off
    return compat.shard_map(shard_fn, mesh=mesh,
                            in_specs=(P(axes, None), P(None, None)),
                            out_specs=(P(None, None), P(None, None)),
                            check_vma=False)(index, q)


def _sharded_topk_1d(scores: jax.Array, k: int, mesh: Mesh):
    """Top-k over a 1-D score vector sharded over every mesh axis."""
    from repro.core.index import _topk_merge
    axes = tuple(mesh.axis_names)
    ndev = int(np.prod(mesh.devices.shape))
    rows_per = scores.shape[0] // ndev

    def shard_fn(s_local):
        pos = jax.lax.axis_index(axes)
        kk = min(k, s_local.shape[0])
        s, ids = jax.lax.top_k(s_local, kk)
        ids = ids + pos * rows_per
        s_all = jax.lax.all_gather(s[None], axes, axis=1, tiled=True)
        i_all = jax.lax.all_gather(ids[None], axes, axis=1, tiled=True)
        ms, mi = _topk_merge(s_all, i_all, k)
        return ms[0], mi[0]

    return compat.shard_map(shard_fn, mesh=mesh, in_specs=(P(axes),),
                            out_specs=(P(None), P(None)),
                            check_vma=False)(scores)


BUNDLE_BUILDERS = {
    "lm": lm_bundle,
    "gnn": gnn_bundle,
    "recsys": recsys_bundle,
    "biencoder": biencoder_bundle,
}
