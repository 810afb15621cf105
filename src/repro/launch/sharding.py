"""Logical-axis sharding for the serving/training substrate.

Models annotate activations with *logical* axis names ("batch", "heads",
"ff", "kv_seq", ...).  A :class:`ShardingContext` maps logical names to mesh
axes and applies ``with_sharding_constraint``; outside a context (CPU smoke
tests) the annotations are no-ops, so the same model code runs everywhere.

Parameter sharding is path-based (:func:`param_pspecs`) — rules keyed on the
parameter's leaf name, MaxText-style, so new blocks get sensible default
sharding without touching the launcher.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()

# default logical-axis -> mesh-axis rules (single-pod mesh ('data','model'))
DEFAULT_RULES: Dict[str, Optional[object]] = {
    "batch": "data",        # replaced by ('pod','data') on the multi-pod mesh
    "seq": None,            # sequence usually replicated...
    "kv_seq": "model",      # ...but decode KV caches shard sequence on model
    "heads": "model",
    "kv_heads": None,       # kv heads can be tiny (MQA kv=1): replicate
    "ff": "model",
    "expert": "model",
    "vocab": "model",
    "embed": None,
    "hidden": None,
    "rec": "model",         # recurrent width (RG-LRU / xLSTM projections)
}


class ShardingContext:
    def __init__(self, mesh: Mesh, rules: Optional[Dict[str, object]] = None):
        self.mesh = mesh
        self.rules = dict(DEFAULT_RULES)
        if rules:
            self.rules.update(rules)
        # on a multi-pod mesh, "batch" spans both pod and data axes
        if "pod" in mesh.axis_names and self.rules.get("batch") == "data":
            self.rules["batch"] = ("pod", "data")

    def spec(self, *logical: Optional[str]) -> P:
        axes = []
        for name in logical:
            axes.append(None if name is None else self.rules.get(name))
        return P(*axes)

    def sharding(self, *logical: Optional[str]) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(*logical))


def current() -> Optional[ShardingContext]:
    return getattr(_state, "ctx", None)


@contextlib.contextmanager
def use(ctx: Optional[ShardingContext]):
    prev = getattr(_state, "ctx", None)
    _state.ctx = ctx
    try:
        yield ctx
    finally:
        _state.ctx = prev


def act(x, *logical: Optional[str]):
    """Constrain an activation's sharding by logical axis names (no-op when
    no context is active, e.g. in CPU smoke tests)."""
    ctx = current()
    if ctx is None or x.ndim != len(logical):
        return x
    return jax.lax.with_sharding_constraint(x, ctx.sharding(*logical))


# ---------------------------------------------------------------------------
# parameter sharding rules (by leaf name)
# ---------------------------------------------------------------------------

# leaf-name -> which dim (negative ok) gets the 'model' axis.  Everything
# else is replicated.  Dims are relative to the *unstacked* param; a leading
# scan-layer axis is detected by path prefix and skipped.
_COL_SHARDED = {  # shard output dim (last)
    "wq", "wk", "wv", "w_up", "w_gate", "w_in", "w_z", "head",
    "w_gate_in", "w_proj",
}
_ROW_SHARDED = {  # shard input dim (first of the matmul = -2)
    "wo", "w_down", "w_out",
}
_EXPERT_SHARDED = {  # MoE stacked expert weights: shard expert dim (dim 0)
    "we_up", "we_gate", "we_down",
}
_VOCAB_SHARDED = {"embed"}  # (V, d) or (K, V, d): shard the V dim


def _leaf_spec(path: Tuple[str, ...], shape: Tuple[int, ...],
               axis_sizes: Optional[Dict[str, int]] = None) -> P:
    """2D weight sharding: tensor-parallel on 'model' plus FSDP on 'data'.

    The 132B-scale archs do not fit at 16-way TP alone (16.5 GB/chip of
    bf16 weights + 4x that in f32 optimizer state), so every matrix also
    shards its other dim over 'data' (ZeRO-3 style; GSPMD inserts the
    per-layer all-gathers).  Pods replicate weights (pure DP across pods).

    Every assignment is divisibility-guarded against the mesh axis sizes
    (NamedSharding on concrete arrays forbids uneven partitions — e.g.
    Mixtral's 8 experts on a 16-way 'model' axis fall back to sharding
    d_ff instead).
    """
    name = path[-1]
    stacked = "scan" in path  # scan-over-layers stacked leading axis
    off = 1 if stacked else 0
    spec = [None] * len(shape)

    def put(dim: int, axis: str) -> bool:
        if spec[dim % len(shape)] is not None:
            return False
        if axis_sizes is not None:
            size = axis_sizes.get(axis, 1)
            if size > 1 and shape[dim % len(shape)] % size != 0:
                return False
        spec[dim % len(shape)] = axis
        return True

    if name in _COL_SHARDED and len(shape) - off >= 2:
        put(-1, "model")
        put(-2, "data")
    elif name in _ROW_SHARDED and len(shape) - off >= 2:
        put(-2, "model")
        put(-1, "data")
    elif name in _EXPERT_SHARDED:
        # prefer expert-parallel; fall back to d_ff tensor parallel
        if not put(off, "model"):
            put(-1 if name in ("we_up", "we_gate") else -2, "model")
        # FSDP dim: d for we_up/we_gate; whichever of (f, d) is free for
        # we_down (both must stay sharded or a 132B-scale expert stack
        # leaves multi-GB per chip — caught by the dry-run memory check)
        if not put(off + 1, "data"):
            put(-1, "data")
    elif name in _VOCAB_SHARDED:
        # (V, d) or (K, V, d) for multi-codebook embeds: V is always dim -2
        put(-2, "model")
        put(-1, "data")
    # biases, norm scales, routers, lru params: replicated
    return P(*spec)


def param_pspecs(params, mesh: Optional[Mesh] = None) -> object:
    """PartitionSpec pytree matching ``params`` (path-based rules)."""
    axis_sizes = dict(mesh.shape) if mesh is not None else None
    flat = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for keypath, leaf in flat[0]:
        path = tuple(
            getattr(k, "key", getattr(k, "name", str(k))) for k in keypath)
        path = tuple(str(p) for p in path)
        specs.append(_leaf_spec(path, leaf.shape, axis_sizes))
    return jax.tree_util.tree_unflatten(flat[1], specs)


def param_shardings(mesh: Mesh, params) -> object:
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), param_pspecs(params, mesh),
        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# layer-state (KV cache / recurrent state) sharding rules
# ---------------------------------------------------------------------------


def _fits(axes, n: int, axis_sizes: Optional[Dict[str, int]]) -> bool:
    """Whether mesh ``axes`` (one name or a tuple) split a dim of ``n``
    evenly — NamedSharding on concrete arrays forbids uneven partitions."""
    if axis_sizes is None:
        return True
    size = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        size *= axis_sizes.get(a, 1)
    return size <= 1 or n % size == 0


def _state_leaf_spec(path: Tuple[str, ...], shape: Tuple[int, ...],
                     batch_axes,
                     axis_sizes: Optional[Dict[str, int]] = None) -> P:
    name = path[-1]
    stacked = "scan" in path
    off = 1 if stacked else 0
    nd = len(shape) - off
    spec = [None] * len(shape)
    if name == "pos" or nd == 0:
        return P(*spec)

    def put(dim: int, axes) -> None:
        # divisibility guard: a dim the mesh axes don't divide falls back
        # to replication (e.g. a 6-lane dense pool on 4-way 'data')
        if _fits(axes, shape[dim], axis_sizes):
            spec[dim] = axes

    if name in ("k_pages", "v_pages") and nd == 4:
        # paged pool (pages, KV, page_size, hd): block tables hold global
        # page ids, so every shard keeps all pages (replicated over the
        # batch axes) and KV heads split over 'model' — see
        # paged_decode_pspecs
        put(off + 1, "model")
        return P(*spec)
    put(off, batch_axes)  # leading real dim is always batch
    if name in ("k", "v", "k_scale", "v_scale") and nd == 4:
        put(off + 2, "model")         # KV cache: shard the sequence dim
    elif name == "C" and nd == 4:
        put(off + 2, "model")         # mLSTM matrix memory: shard head_dim
    elif name == "n" and nd == 3:
        put(off + 2, "model")
    # (B, d)-shaped scalars (slstm c/n/h/m, rglru h) and conv buffers:
    # batch-sharded only.
    return P(*spec)


def state_pspecs(states, mesh: Optional[Mesh] = None,
                 batch_axes="__auto__") -> object:
    if batch_axes == "__auto__":
        batch_axes = ("pod", "data") if (mesh is not None and
                                         "pod" in mesh.axis_names) else "data"
    axis_sizes = dict(mesh.shape) if mesh is not None else None
    flat = jax.tree_util.tree_flatten_with_path(states)
    specs = []
    for keypath, leaf in flat[0]:
        path = tuple(
            str(getattr(k, "key", getattr(k, "name", str(k))))
            for k in keypath)
        specs.append(_state_leaf_spec(path, leaf.shape, batch_axes,
                                      axis_sizes))
    return jax.tree_util.tree_unflatten(flat[1], specs)


def paged_decode_pspecs(ctx: ShardingContext, lanes: int,
                        pool_shape: Tuple[int, ...]) -> Tuple[P, P, P, P]:
    """``shard_map`` specs of one paged decode kernel call under ``ctx``:
    (page pool, query/output heads, block tables, lengths).

    The pool spec is the one ``state_pspecs`` gives a layer's
    ``k_pages``/``v_pages``: every page on every shard, KV heads over
    'model' where it divides them.  Query heads follow their KV heads
    (head h reads KV head h // G, so contiguous splits line up).  Lanes
    split over the batch axes where those divide ``lanes``; the pool is
    replicated over them, so on a mesh with data > 1 every data replica
    holds the whole pool."""
    sizes = dict(ctx.mesh.shape)
    pool = _state_leaf_spec(("k_pages",), tuple(pool_shape), None, sizes)
    rows = ctx.rules.get("batch")
    names = rows if isinstance(rows, tuple) else (rows,)
    if rows is None or not set(names) <= set(ctx.mesh.axis_names) \
            or not _fits(rows, lanes, sizes):
        rows = None
    return pool, P(rows, pool[1], None), P(rows, None), P(rows)


def state_shardings(mesh: Mesh, states) -> object:
    """NamedSharding pytree for KV / recurrent serving state on ``mesh``
    (divisibility-guarded: indivisible dims replicate)."""
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec),
        state_pspecs(states, mesh),
        is_leaf=lambda x: isinstance(x, P))
