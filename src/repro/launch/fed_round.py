"""The FedSTIL parameter server as an on-mesh collective program.

At pod scale the "parameter server" is not a process — clients live along
the data axis (one edge client per data row; pods = spatial regions), their
adaptive-layer pytrees are TP-sharded along the model axis, and one
federated round (paper Algorithm 1, lines 5-9) is a single SPMD program:

  1. every client's task feature (mean prototype, Eq. 3) is all-gathered
     over the client axis (tiny: proto_dim floats per client);
  2. task similarity (Eq. 4, KL) + decayed relevance W (Eq. 5) are computed
     replicated (C x C, tiny);
  3. personalized aggregation B_i = sum_j W_ij theta_j (Eq. 6) is ONE
     ``psum_scatter`` over the client axis: client j contributes the
     outer-scaled stack W[:, j] * theta_j and receives exactly its own B_i.
     Wire bytes/client = (C-1)/C * C * |theta| ~= C * |theta| — the same as
     the WAN cost in the paper's Table II, now over ICI.

Run a CPU demo:   PYTHONPATH=src python -m repro.launch.fed_round --demo
Dry-run at scale: PYTHONPATH=src python -m repro.launch.fed_round \
                      --arch qwen3-1.7b

``--trace out.jsonl`` records a repro.obs span per action (demo /
stacked-demo / lower, device-synced wall time each); inspect with
``python -m repro.obs.report out.jsonl`` or export a Perfetto trace via
``--chrome``. ``--profile DIR`` runs the traced actions inside
``jax.profiler.trace(DIR)``: the device trace holds the spans on its own
clock, and ``perf/trace_reduce.reduce(load(<DIR>/plugins/profile/*/
*.xplane.pb), window="fed_round.demo", kernels={})`` reads device busy
time and idle time by span from it.
"""
import os as _os
import sys as _sys
if "--demo" in _sys.argv or "--stacked-demo" in _sys.argv:
    _os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
else:
    _os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.common.compat import shard_map
from repro.common.compile_cache import enable_compile_cache
from repro.common.pytree import tree_flatten_concat, tree_unflatten_concat
from repro.core.fedstil import sharded_fused_aggregate
from repro.core.relevance import decayed_relevance
from repro.obs import trace as obs
from repro.sharding.specs import engine_mesh


def fed_round(theta_local, task_feature_local, hist_features_local, *,
              client_axis: str, forgetting_ratio: float = 0.5):
    """One FedSTIL round from inside shard_map.

    theta_local: this client's adaptive pytree (may itself be TP-sharded —
        the aggregation is leaf-wise elementwise so TP shards aggregate
        independently, no model-axis collective needed!).
    task_feature_local: (D,) this client's current task feature.
    hist_features_local: (k, D) this client's last-k task features
        (most recent last).
    Returns (B_local: same pytree = this client's personalized base,
             W_row: (C,) this client's relevance row).
    """
    me = lax.axis_index(client_axis)

    # (1) gather every client's historical task features (tiny)
    hist = lax.all_gather(hist_features_local, client_axis)      # (C, k, D)
    C, k = hist.shape[0], hist.shape[1]

    # (2) Eq. 4/5 via the shared batched primitive: decayed similarity of
    # MY current task vs THEIR histories (hist is most-recent-last, so the
    # decay vector is reversed). "ref" keeps the lowering free of
    # pallas_call so the same program compiles on any mesh backend.
    decay = forgetting_ratio ** jnp.arange(k - 1, -1, -1, jnp.float32)
    w_row = decayed_relevance(task_feature_local[None], hist, decay,
                              metric="kl", backend="ref")[0]     # (C,)
    w_row = jnp.where(jnp.arange(C) == me, 0.0, w_row)           # j != i
    w_row = w_row / jnp.maximum(jnp.sum(w_row), 1e-9)

    # full W needed so every j knows its column: gather the rows (C x C)
    W = lax.all_gather(w_row, client_axis)                       # (C, C)

    # (3) Eq. 6 as ONE reduce-scatter over the client axis:
    # my contribution to every destination i is W[i, me] * theta_me
    flat, meta = tree_flatten_concat(theta_local)
    contrib = W[:, me][:, None] * flat[None, :]                  # (C, P_loc)
    mine = lax.psum_scatter(contrib, client_axis,
                            scatter_dimension=0, tiled=False)    # (P_loc,)
    B_local = tree_unflatten_concat(mine.astype(flat.dtype), meta)
    return B_local, w_row


def fed_round_hierarchical(theta_local, task_feature_local,
                           hist_features_local, *, client_axis: str,
                           pod_axis: str, beta: float = 0.25,
                           forgetting_ratio: float = 0.5):
    """Multi-pod FedSTIL: pods = spatial regions of edge clients.

    Within-pod: full Eq. 4-6 (KL relevance over ICI). Cross-pod: a single
    pmean of the pod-level bases over DCN, mixed in with weight ``beta`` —
    distant regions share *general* knowledge while the fine-grained
    spatial-temporal relevance stays local to the region. Cross-pod traffic
    is |theta| per round instead of the flat C_total x |theta| (the same
    comm-efficiency argument the paper makes for the WAN, one level up).
    """
    B_local, w_row = fed_round(theta_local, task_feature_local,
                               hist_features_local, client_axis=client_axis,
                               forgetting_ratio=forgetting_ratio)
    B_global = jax.tree.map(lambda l: lax.pmean(l, pod_axis), B_local)
    B_mixed = jax.tree.map(lambda a, b: (1.0 - beta) * a + beta * b,
                           B_local, B_global)
    return B_mixed, w_row


# ---------------------------------------------------------------------------
# CLI: demo + production lowering
# ---------------------------------------------------------------------------


def _demo():
    """8 host devices, 4 clients x TP2: verify against the numpy server."""
    mesh = engine_mesh(jax.devices()[:8], model=2)
    C, D, Pn, k = 4, 16, 64, 3
    key = jax.random.PRNGKey(0)
    thetas = jax.random.normal(key, (C, Pn))
    feats = jax.random.normal(jax.random.PRNGKey(1), (C, D))
    hists = jax.random.normal(jax.random.PRNGKey(2), (C, k, D))

    def step(theta, feat, hist):
        # theta local: (1, P/tp) — this client's row
        th = {"w": theta[0]}
        B, w_row = fed_round(th, feat[0], hist[0], client_axis="data")
        return B["w"][None], w_row[None]

    fn = jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(P("data", "model"), P("data", None), P("data", None, None)),
        out_specs=(P("data", "model"), P("data", None))))
    with jax.set_mesh(mesh):
        B, W = fn(thetas, feats, hists)

    # reference server: the same batched code the parameter server runs
    # (core.relevance + the Pallas Eq. 6 kernel in interpret mode)
    from repro.core.relevance import normalize_rows
    from repro.kernels import ops
    decay = 0.5 ** jnp.arange(k - 1, -1, -1, jnp.float32)
    Wref = np.array(decayed_relevance(feats, hists, decay,
                                      metric="kl", backend="ref"))
    np.fill_diagonal(Wref, 0.0)
    Wref = normalize_rows(Wref)
    Bref = np.asarray(ops.relevance_aggregate(
        jnp.asarray(Wref), thetas, backend="interpret"))
    np.testing.assert_allclose(np.asarray(W), Wref, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(B), Bref, rtol=1e-3, atol=1e-4)
    print("fed_round on-mesh == batched parameter server  (W, B match)")
    print("W =\n", np.round(np.asarray(W), 3))


def _lower(arch: str, multi_pod: bool):
    """Lower a production federated round: 16 clients (data axis), each
    client's adaptive layers TP-sharded over the model axis."""
    from repro.configs import get_config
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import abstract_train_state
    from repro.sharding import specs as SPECS

    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    tp = mesh.shape["model"]
    C = mesh.shape["data"] * (mesh.shape["pod"] if multi_pod else 1)
    c_axes = ("pod", "data") if multi_pod else "data"
    _, B0, trainable, _ = abstract_train_state(cfg, tp)
    D, k = 256, 6

    # per-client adaptive pytrees: leading C dim sharded over the data axis
    theta = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((C,) + l.shape, l.dtype), B0)

    def step(theta_c, feat, hist):
        th = jax.tree.map(lambda l: l[0], theta_c)   # my client's slice
        if multi_pod:
            B, w = fed_round_hierarchical(th, feat[0], hist[0],
                                          client_axis="data", pod_axis="pod")
        else:
            B, w = fed_round(th, feat[0], hist[0], client_axis="data")
        return (jax.tree.map(lambda l: l[None], B), w[None])

    base_sp = SPECS.tree_param_specs(cfg, B0, tp_size=tp)
    sp = jax.tree.map(lambda spec: P(*((c_axes,) + tuple(spec))), base_sp,
                      is_leaf=lambda x: isinstance(x, P))
    in_specs = (sp, P(c_axes, None), P(c_axes, None, None))
    out_specs = (sp, P(c_axes, None))
    fn = jax.jit(shard_map(step, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False))
    feats = jax.ShapeDtypeStruct((C, D), jnp.float32)
    hists = jax.ShapeDtypeStruct((C, k, D), jnp.float32)
    with jax.set_mesh(mesh):
        compiled = fn.lower(theta, feats, hists).compile()
    from repro.sharding.analysis import parse_collectives
    coll = parse_collectives(compiled.as_text())
    print(f"fed_round lowered for {arch} on {'2x16x16' if multi_pod else '16x16'}")
    print(f"  adaptive payload/client: "
          f"{sum(np.prod(l.shape) * l.dtype.itemsize for l in jax.tree.leaves(theta))/C/1e6:.1f} MB")
    print(f"  collective bytes/device: {coll.total_bytes/1e6:.2f} MB "
          f"{coll.count_by_kind}")


def _stacked_demo():
    """8 host devices, C=64 clients sharded 4-way × P sharded 2-way: the
    engine's mesh-sharded fused aggregate (``core.fedstil``, the one
    sharded implementation) matches the single-device kernel path."""
    from repro.kernels import ops

    mesh = engine_mesh(jax.devices()[:8], model=2)
    C, Pn = 64, 4096
    w = jnp.abs(jax.random.normal(jax.random.PRNGKey(0), (C, C)))
    thetas = jax.random.normal(jax.random.PRNGKey(1), (C, Pn))
    B, Wn = sharded_fused_aggregate(w, thetas, mesh)
    Bref, Wnref = ops.fused_relevance_aggregate(w, thetas, backend="ref")
    np.testing.assert_allclose(np.asarray(Wn), np.asarray(Wnref),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(B), np.asarray(Bref),
                               rtol=1e-4, atol=1e-5)
    print(f"sharded fused aggregate (C={C} over data×{mesh.shape['data']}, "
          f"P={Pn} over model×{mesh.shape['model']}) == kernel path")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--demo", action="store_true")
    ap.add_argument("--stacked-demo", action="store_true")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--trace", default=None, metavar="OUT.jsonl",
                    help="write a repro.obs telemetry JSONL (one span per "
                         "action); read it with python -m repro.obs.report")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="record a jax.profiler device trace of the traced "
                         "actions into DIR")
    args = ap.parse_args()
    enable_compile_cache()
    tracer = (obs.Tracer(path=args.trace) if args.trace or args.profile
              else obs.NullTracer())
    profile = (jax.profiler.trace(args.profile) if args.profile
               else contextlib.nullcontext())
    try:
        with obs.active(tracer), profile:
            if args.stacked_demo:
                with obs.span("fed_round.stacked_demo", cat="phase"):
                    _stacked_demo()
                if not (args.demo or args.arch):
                    return
            if args.demo or not args.arch:
                with obs.span("fed_round.demo", cat="phase"):
                    _demo()
            if args.arch:
                with obs.span("fed_round.lower", cat="phase", arch=args.arch):
                    _lower(args.arch, args.multi_pod)
    finally:
        tracer.close()
        if args.trace:
            print(f"telemetry: {args.trace}  "
                  f"(python -m repro.obs.report {args.trace})")


if __name__ == "__main__":
    main()
