"""Sharding rules engine: divisibility fallback, logical axes, family rules."""
import jax
from jax.sharding import PartitionSpec as P

from repro.par.compat import abstract_mesh
from repro.par.sharding import (
    ShardingRules,
    gnn_rules,
    lm_rules,
    logical_to_physical,
    recsys_rules,
    spec_for,
)

# rules resolve against mesh *shape* only — an abstract mesh needs no
# devices
MESH2 = abstract_mesh((1, 2), ("data", "model"))


def test_logical_axes():
    assert logical_to_physical("dp", MESH2) == ("data",)
    assert logical_to_physical("tp", MESH2) == ("model",)
    assert logical_to_physical("fsdp", MESH2) == ("data", "model")
    m3 = abstract_mesh((1, 1, 2), ("pod", "data", "model"))
    assert logical_to_physical("dp", m3) == ("pod", "data")


def test_divisibility_fallback():
    rules = ShardingRules([(r"w$", [((0, "tp"),)]), (r".*", [()])])
    # 4 % 2 == 0 -> sharded; 3 % 2 != 0 -> replicated
    assert rules.spec("a/w", (4, 8), MESH2) == P("model", None)
    assert rules.spec("a/w", (3, 8), MESH2) == P()


def test_clause_group_ordering():
    # first group that FULLY fits wins; others ignored
    rules = ShardingRules([
        (r"moe$", [((0, "ep"), (1, "dp")), ((1, "tp"),)]),
        (r".*", [()]),
    ])
    # group 1 fits (E=4 % 2, ff=2 % 1(data))
    assert rules.spec("moe", (4, 2), MESH2) == P("model", "data")
    # E=3 doesn't divide: falls to group 2 on dim 1
    assert rules.spec("moe", (3, 8), MESH2) == P(None, "model")


def test_lm_rules_2d_fsdp_tp():
    rules = lm_rules()
    # (L, d, out): out over model + d over data(=1 here, divides)
    spec = rules.spec("layers/attn/wq/w", (4, 64, 128), MESH2)
    assert spec == P(None, "data", "model")
    # embed: vocab over model, d over data
    assert rules.spec("embed", (1000, 64), MESH2) == P("model", "data")


def test_lm_rules_smollm_fallbacks():
    # 16-wide model axis vs 9-head smollm: fused proj (576) shards,
    # per-head reshape never sees a 9-way constraint
    mesh16 = abstract_mesh((1, 16), ("data", "model"))
    rules = lm_rules()
    spec = rules.spec("layers/attn/wq/w", (30, 576, 576), mesh16)
    assert spec == P(None, "data", "model")


def test_moe_rules_ep_vs_tp():
    mesh16 = abstract_mesh((1, 16), ("data", "model"))
    rules = lm_rules(moe=True)
    # arctic: 128 experts % 16 == 0 -> EP (+ ff over dp)
    assert rules.spec("layers/moe/w1", (35, 128, 7168, 4864), mesh16) \
        == P(None, "model", None, "data")
    # mixtral: 8 experts % 16 != 0 -> falls to TP-inside-expert
    spec = rules.spec("layers/moe/w1", (32, 8, 4096, 14336), mesh16)
    assert spec == P(None, None, "data", "model")


def test_recsys_rules_fsdp_tables():
    rules = recsys_rules()
    assert rules.spec("tables/0", (1024, 128), MESH2) == P(("data", "model"), None)
    assert rules.spec("user_embed", (2048, 64), MESH2) == P(("data", "model"), None)


def test_gnn_rules_replicate():
    rules = gnn_rules()
    assert rules.spec("layers/edge_mlp/0/w", (16, 48, 16), MESH2) == P()


def test_spec_for_tree():
    tree = {"embed": jax.ShapeDtypeStruct((100, 4), "float32"),
            "norm": {"scale": jax.ShapeDtypeStruct((7,), "float32")}}
    specs = spec_for(tree, MESH2, lm_rules())
    assert specs["embed"] == P("model", "data")
    assert specs["norm"]["scale"] == P()
