"""Sharding rules: map every parameter / batch / cache leaf to a
PartitionSpec by its tree path (Megatron TP + optional FSDP over data).

The model code (repro/models) consumes *local* shards inside shard_map and
emits collectives via AxisCtx; these specs define the global layout the
dry-run hands to jax.jit/shard_map.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig

# stacked-subtree prefixes (leading layer dim)
_STACKED = ("layers", "adaptive_layers", "enc_layers")


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_spec(cfg: ModelConfig, path: str, shape, *, tp_axis="model",
               fsdp_axis: Optional[str] = "data", tp_size: int = 16) -> P:
    """PartitionSpec for one parameter leaf, identified by its path string.

    The path may be prefixed arbitrarily (trainable/alpha/..., opt m/v, B) —
    rules match on the trailing components.
    """
    fs = fsdp_axis if cfg.fsdp else None
    stacked = any(s in path.split("/") for s in _STACKED)
    kv_sharded = cfg.n_kv_heads >= tp_size  # else replicated + group-sliced

    def lead(*spec):
        return P(*( (None,) + spec if stacked else spec ))

    name = path.split("/")[-1]
    parent = path.split("/")[-2] if "/" in path else ""

    # ---- attention ----
    if parent in ("attn", "cross"):
        if name == "wq":
            return lead(fs, tp_axis)
        if name in ("wk", "wv"):
            return lead(fs, tp_axis) if kv_sharded else lead(fs, None)
        if name == "wo":
            return lead(tp_axis, fs)
        if name == "bq":
            return lead(tp_axis)
        if name in ("bk", "bv"):
            return lead(tp_axis) if kv_sharded else lead(None)
        if name in ("qnorm", "knorm"):
            return lead(None)

    # ---- dense mlp / moe dense residual ----
    if parent in ("mlp", "dense"):
        if name in ("wi", "wg"):
            return lead(fs, tp_axis)
        if name == "wo":
            return lead(tp_axis, fs)

    # ---- moe experts ----
    if parent == "moe":
        if name == "router":
            return lead(None, None)
        if name in ("wi", "wg"):                    # (E, d, f)
            return lead(tp_axis, None, fs)
        if name == "wo":                            # (E, f, d)
            return lead(tp_axis, fs, None)
    if "moe/dense" in path:
        pass  # handled by parent == "dense"

    # ---- mamba ----
    if parent == "mamba":
        if name in ("w_zx", "w_dt"):
            return lead(fs, tp_axis)
        if name == "w_bc":
            return lead(fs, None)
        if name in ("dt_bias", "A_log", "D", "conv_b", "norm"):
            return lead(tp_axis)
        if name == "conv_w":
            return lead(None, tp_axis)
        if name == "w_out":
            return lead(tp_axis, fs)

    # ---- rwkv time/channel mix ----
    if parent == "time":
        if name in ("wr", "wk", "wv", "wg"):
            return lead(fs, tp_axis)
        if name == "wo":
            return lead(tp_axis, fs)
        if name in ("u", "ln_scale", "ln_bias"):
            return lead(tp_axis)
        if name in ("mu", "w0", "Aw", "Bw"):
            return lead(*([None] * (len(shape) - (1 if stacked else 0))))
    if parent == "chan":
        if name == "wk":
            return lead(fs, tp_axis)
        if name == "wv":
            return lead(tp_axis, fs)
        if name in ("wr", "mu"):
            return lead(*([None] * (len(shape) - (1 if stacked else 0))))

    # ---- embedding / head ----
    if parent == "embed" and name == "table":
        return P(tp_axis, None)
    if parent == "head" and name == "w":
        return P(None, tp_axis)

    # ---- norms, scalars, anything else: replicated ----
    return P(*([None] * len(shape)))


def tree_param_specs(cfg: ModelConfig, tree, **kw):
    """PartitionSpec pytree matching ``tree`` (of arrays/ShapeDtypeStructs)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    specs = [param_spec(cfg, _path_str(path), leaf.shape, **kw)
             for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


# ---------------------------------------------------------------------------
# federated engine mesh: the layout source of truth for engine="sharded"
# ---------------------------------------------------------------------------
#
# Axis names are fixed repo-wide: "data" shards the client dim (every
# stacked (C, ...) leaf puts its leading dim here), "model" shards the
# flattened parameter dim of the (C, P) server matrices. On the CPU/host
# meshes we run today model=1 (P stays whole per device); the axis exists
# so the layout generalizes to real multi-chip meshes without respelling
# any spec.

ENGINE_AXES = ("data", "model")


def auto_mesh(shape, names, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: jit programs over it are
    partitioned by the compiler. (``make_mesh`` defaults to ``Explicit``
    axes, under which a dot over a sharded contraction — the Eq. 6
    aggregate, the KL similarity — needs an explicit out_sharding.) Every
    mesh in the repo is built here."""
    return jax.make_mesh(tuple(shape), tuple(names),
                         axis_types=(AxisType.Auto,) * len(names),
                         devices=devices)


def engine_mesh(devices=None, *, model: int = 1):
    """The engine's Mesh(("data", "model")): all devices on the client
    axis by default. ``run_simulation(engine="sharded")`` builds exactly
    this; tests/benches pass an explicit device list to shrink it."""
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if n % model != 0:
        raise ValueError(f"{n} devices not divisible by model={model}")
    return auto_mesh((n // model, model), ENGINE_AXES, devices=devices)


def padded_clients(C: int, mesh) -> int:
    """Smallest Cp >= C divisible by the data-axis size. Clients [C, Cp)
    are padding: zero batches, validity mask 0, never pushed into the
    relevance ring (so their W rows/cols are zero and the nz machinery
    keeps their base untouched)."""
    d = mesh.shape["data"]
    return ((C + d - 1) // d) * d


def client_row_spec(ndim: int, *, client_axis: str = "data") -> P:
    """Leading-client-dim spec: rows over ``client_axis``, rest whole."""
    return P(*((client_axis,) + (None,) * (ndim - 1)))


def stacked_tree_specs(tree, *, client_axis: str = "data"):
    """Spec pytree for any stacked (C, ...) state/batch/buffer pytree:
    every leaf's leading client dim over ``client_axis``."""
    return jax.tree.map(
        lambda l: client_row_spec(l.ndim, client_axis=client_axis), tree)


def named_shardings(mesh, spec_tree):
    """PartitionSpec pytree -> NamedSharding pytree on ``mesh``."""
    return jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda s: isinstance(s, P))


# ---------------------------------------------------------------------------
# federated server: stacked (C, P) aggregate specs
# ---------------------------------------------------------------------------


def stacked_aggregate_specs(*, client_axis: str = "data",
                            param_axis: Optional[str] = "model"):
    """PartitionSpecs for the sharded server aggregate B = Wn @ Θ
    (``core.fedstil.sharded_aggregate_fn``).

    Everything keeps its client rows on ``client_axis``: Θ (C, P) (each
    device holds a client block resident between rounds, parameter
    columns optionally over ``param_axis``), the raw relevance W (C, C)
    and its normalized Wn (a row needs only itself to normalize), and the
    output B, so each device ends the round holding exactly its own
    clients' new bases. Eq. 6 mixes every client into every row, so the
    program all-gathers Θ over the client axis for the contraction.
    """
    return {
        "w": P(client_axis, None),
        "thetas": P(client_axis, param_axis),
        "out": P(client_axis, param_axis),
        "wn": P(client_axis, None),
    }


def stacked_eval_specs(*, client_axis: str = "data"):
    """PartitionSpecs for the batched (C x tasks) retrieval eval at C ≫ 1000.

    Every input and output carries a leading client dim sharded over
    ``client_axis``; the task/query/gallery content dims stay unsharded.
    Each device then evaluates its own block of clients end-to-end (feature
    heads, distance matrices, ranking, metrics) with NO cross-client
    collectives — retrieval eval is embarrassingly parallel over clients,
    unlike the Eq. 6 aggregate which contracts the client dim.
    """
    def row(nd):
        return P(*((client_axis,) + (None,) * (nd - 1)))

    return {
        "qf": row(4),          # (C, T, Q, D) query prototypes/features
        "qids": row(3),        # (C, T, Q)
        "task_mask": row(2),   # (C, T)
        "gf": row(3),          # (C, G, D) gallery prototypes/features
        "gids": row(2),        # (C, G)
        "gmask": row(2),       # (C, G)
        "metrics": row(2),     # (C, T) per metric key
    }


def serving_index_specs(*, client_axis: str = "data"):
    """PartitionSpecs for the serving index's device image (repro.serving).

    Same shape contract as the stacked eval: EVERY resident array —
    query batches, the flat int8 image, and the IVF bucket image
    (centroids, bucket-major codes, packed sidecar, inverted lists) —
    leads with the client dim, row-sharded over ``client_axis``. Each
    device serves its own block of clients' galleries end-to-end
    (featurize, cluster-assign, shortlist, rank) with no cross-client
    collectives; bucket/row content dims stay unsharded.
    """
    def row(nd):
        return P(*((client_axis,) + (None,) * (nd - 1)))

    return {
        # query operands
        "qp": row(3),          # (C, B, proto_dim)
        "qmask": row(2),       # (C, B)
        "bn_mu": row(2),       # (C, F)
        "bn_sd": row(2),       # (C, F)
        # flat image (exact int8/fp32 paths)
        "gq": row(3),          # (C, G, F) int8 codes
        "gscale": row(2),      # (C, G)
        "gn2": row(2),         # (C, G)
        "gids": row(2),        # (C, G)
        "gf": row(3),          # (C, G, F) optional fp32 rows
        # IVF image (approximate path)
        "cent": row(3),        # (C, nlist, F)
        "cn2": row(2),         # (C, nlist)
        "bq": row(4),          # (C, nlist, bcap, F) int8 bucket rows
        "pack": row(4),        # (C, nlist, 3, bcap) packed sidecar
        "binv": row(3),        # (C, nlist, bcap) inverted lists
    }


def stacked_eval_theta_specs(theta, *, client_axis: str = "data"):
    """PartitionSpec pytree for a stacked (C, ...) eval-theta pytree:
    client rows over ``client_axis``, everything else replicated."""
    return jax.tree.map(
        lambda l: P(*((client_axis,) + (None,) * (l.ndim - 1))), theta)


def batch_axes(global_batch: int, dp: int, multi_pod: bool):
    """Which axes the batch dim shards over (None if not divisible)."""
    axes = ("pod", "data") if multi_pod else ("data",)
    total = dp * (2 if multi_pod else 1)
    if global_batch % total == 0:
        return axes if multi_pod else "data"
    if global_batch % dp == 0:   # shard over data only
        return "data"
    return None                   # replicate (long_500k batch=1)


def batch_specs(cfg: ModelConfig, batch_tree, global_batch: int, dp: int,
                multi_pod: bool):
    b = batch_axes(global_batch, dp, multi_pod)

    def spec_for(path, leaf):
        return P(*((b,) + (None,) * (len(leaf.shape) - 1)))

    flat, treedef = jax.tree_util.tree_flatten_with_path(batch_tree)
    specs = [spec_for(p, l) for p, l in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def cache_specs(cfg: ModelConfig, cache_tree, global_batch: int, dp: int,
                multi_pod: bool, *, tp_axis="model"):
    """Decode caches: (L, B, S, KV, hd) -> batch over data, SEQ over model
    (flash-decoding layout); SSM states: heads/channels over model."""
    b = batch_axes(global_batch, dp, multi_pod)

    def spec_for(path, leaf):
        ps = _path_str(path)
        name = ps.split("/")[-1]
        nd = len(leaf.shape)
        if name in ("k", "v"):            # (L, B, S, KV, hd)
            return P(None, b, tp_axis, None, None)
        if name in ("k_scale", "v_scale"):  # (L, B, S, KV)
            return P(None, b, tp_axis, None)
        if name == "h":                   # mamba (L, B, nh, hd, ds)
            return P(None, b, tp_axis, None, None)
        if name == "conv":                # (L, B, k-1, di)
            return P(None, b, None, tp_axis)
        if name == "S":                   # rwkv (L, B, nh, hd, hd)
            return P(None, b, tp_axis, None, None)
        if name in ("x_att", "x_ffn"):    # (L, B, d)
            return P(None, b, None)
        return P(*([None] * nd))

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_tree)
    specs = [spec_for(p, l) for p, l in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)
