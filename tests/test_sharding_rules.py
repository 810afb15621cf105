"""Sharding rules: divisibility guards, expert fallbacks, state specs."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch import sharding as shlib
from repro.launch.mesh import make_smoke_mesh


def test_col_row_rules():
    sizes = {"data": 16, "model": 16}
    assert shlib._leaf_spec(("layers", "scan", "mixer", "wq"),
                            (28, 1536, 1536), sizes) == \
        P(None, "data", "model")
    assert shlib._leaf_spec(("layers", "scan", "mixer", "wo"),
                            (28, 1536, 1536), sizes) == \
        P(None, "model", "data")
    assert shlib._leaf_spec(("layers", "flat", "ffn", "w_down"),
                            (8960, 1536), sizes) == P("model", "data")


def test_vocab_rule_with_codebooks():
    sizes = {"data": 16, "model": 16}
    assert shlib._leaf_spec(("embed", "embed"), (151936, 1536), sizes) == \
        P("model", "data")
    assert shlib._leaf_spec(("embed", "embed"), (4, 2048, 2048), sizes) == \
        P(None, "model", "data")


def test_expert_rule_and_fallback():
    sizes = {"data": 16, "model": 16}
    # dbrx: 16 experts divide 16 -> expert parallel
    assert shlib._leaf_spec(("layers", "scan", "ffn", "we_up"),
                            (40, 16, 6144, 10752), sizes) == \
        P(None, "model", "data", None)
    # mixtral: 8 experts don't divide 16 -> fall back to d_ff
    assert shlib._leaf_spec(("layers", "scan", "ffn", "we_up"),
                            (56, 8, 6144, 16384), sizes) == \
        P(None, None, "data", "model")
    assert shlib._leaf_spec(("layers", "scan", "ffn", "we_down"),
                            (56, 8, 16384, 6144), sizes) == \
        P(None, None, "model", "data")


def test_indivisible_dims_replicate():
    sizes = {"data": 16, "model": 16}
    # norm scales / biases replicated
    assert shlib._leaf_spec(("final_norm", "scale"), (1536,), sizes) == \
        P(None)
    # odd dims fall back to replication rather than uneven shards
    assert shlib._leaf_spec(("m", "wq"), (17, 33), sizes) == P(None, None)


def test_state_specs():
    sizes_mesh = None  # only batch_axes used
    spec = shlib._state_leaf_spec(("scan", "k"), (12, 32, 8, 32768, 128),
                                  "data")
    assert spec == P(None, "data", None, "model", None)
    spec = shlib._state_leaf_spec(("flat", "C"), (2, 4, 256, 256), "data")
    assert spec == P("data", None, "model", None)
    spec = shlib._state_leaf_spec(("flat", "pos"), (), "data")
    assert spec == P()
    spec = shlib._state_leaf_spec(("flat", "h"), (2, 1024), "data")
    assert spec == P("data", None)


def test_state_specs_divisibility_guard():
    """An axis that doesn't divide the dim must replicate that dim, not
    emit an uneven NamedSharding (e.g. a 6-lane pool on 4-way 'data')."""
    sizes = {"data": 4, "model": 16}
    # 6 lanes % 4 != 0 -> batch dim replicated; kv_seq 32768 % 16 == 0
    spec = shlib._state_leaf_spec(("scan", "k"), (12, 6, 8, 32768, 128),
                                  "data", sizes)
    assert spec == P(None, None, None, "model", None)
    # seq dim indivisible by 'model' -> replicated, batch still sharded
    spec = shlib._state_leaf_spec(("scan", "k"), (12, 8, 8, 100, 128),
                                  "data", sizes)
    assert spec == P(None, "data", None, None, None)
    # tuple batch axes multiply: ('pod','data') = 8 doesn't divide 6
    spec = shlib._state_leaf_spec(("flat", "h"), (6, 1024),
                                  ("pod", "data"),
                                  {"pod": 2, "data": 4})
    assert spec == P(None, None)


@pytest.mark.parametrize("mesh_shape, kv, lanes, want_rows, want_kv", [
    ((1, 4), 4, 8, "data", "model"),   # starcoder2-7b on four chips
    ((2, 2), 4, 8, "data", "model"),   # lanes split, pool per data replica
    ((2, 2), 4, 3, None, "model"),     # 3 lanes % 2: lanes replicate
    ((1, 4), 2, 8, "data", None),      # 2 KV heads % 4: heads replicate
])
def test_paged_decode_pspecs(mesh_shape, kv, lanes, want_rows, want_kv):
    """The kernel's shard_map specs follow the pool placement state_pspecs
    gives k_pages (all pages everywhere, KV heads over 'model' where they
    divide); query heads follow KV heads; lanes go over 'data' only
    where it divides them."""
    from jax.sharding import AbstractMesh
    mesh = AbstractMesh(mesh_shape, ("data", "model"))
    shape = (4096, kv, 16, 128)
    pool, heads, rows, lens = shlib.paged_decode_pspecs(
        shlib.ShardingContext(mesh), lanes, shape)
    pools = {"k_pages": jax.ShapeDtypeStruct(shape, jnp.bfloat16)}
    assert pool == shlib.state_pspecs(pools, mesh)["k_pages"]
    assert pool == P(None, want_kv, None, None)
    assert heads == P(want_rows, want_kv, None)
    assert rows == P(want_rows, None) and lens == P(want_rows)


def test_state_shardings_on_smoke_mesh():
    """state_shardings builds NamedShardings for every leaf on the mesh."""
    from jax.sharding import NamedSharding
    mesh = make_smoke_mesh()
    states = {"k": jnp.zeros((2, 4, 16, 8)), "pos": jnp.zeros(())}
    shardings = shlib.state_shardings(mesh, states)
    for leaf in jax.tree_util.tree_leaves(
            shardings, is_leaf=lambda x: isinstance(x, NamedSharding)):
        assert isinstance(leaf, NamedSharding)
        assert leaf.mesh == mesh
    placed = jax.device_put(states, shardings)   # shapes must be legal
    assert placed["k"].shape == (2, 4, 16, 8)


def test_param_pspecs_cover_full_tree():
    from repro.configs import get_config, reduced
    from repro.models import init_params
    cfg = reduced(get_config("dbrx-132b"))
    params = init_params(jax.random.key(0), cfg)
    specs = shlib.param_pspecs(params)
    n_leaves = len(jax.tree_util.tree_leaves(params))
    n_specs = len(jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P)))
    assert n_leaves == n_specs


def test_sharding_context_rules():
    mesh = make_smoke_mesh()
    ctx = shlib.ShardingContext(mesh)
    assert ctx.spec("batch", None, "ff") == P("data", None, "model")
    # no active context -> act() is a no-op
    x = jnp.ones((2, 2))
    assert shlib.act(x, "batch", None) is x
