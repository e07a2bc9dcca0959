"""Manifest: registers the jitted closures that only exist at runtime.

The decorator in ``registry`` covers module-level programs; the engines'
hottest programs, though, are closures built per-strategy-instance
(``Strategy._stacked_train_fn``, ``FedSTIL._server_fns``) or
per-payload-size (``comm.batched.BatchedCodec``'s encode/decode jits).
This module constructs them with tiny concrete configs (bench-scale
abstract shapes, C=100 where the BENCH_*.json sweeps top out) and
registers the *production* jitted callables — so the donation lint sees
the real ``donate_argnums`` and the dtype/callback lints see the real
trace, not a re-implementation.

Importing this module (``registry.load_all()`` does) performs the
registrations; everything here is host-side init at toy sizes, no real
training step ever runs.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.analysis.registry import register_runtime
from repro.sharding.specs import engine_mesh

_SDS = jax.ShapeDtypeStruct
_F32 = jnp.float32
_I32 = jnp.int32

# bench-scale abstract sizes (the BENCH_*.json sweeps top out at C=100)
_C = 100
_HIST = 6


def _sds_like(tree):
    return jax.tree.map(lambda l: _SDS(l.shape, l.dtype), tree)


def _register_fedstil() -> None:
    import numpy as np

    from repro.core.edge_model import EdgeModelConfig
    from repro.core.fedstil import FedSTIL

    cfg = EdgeModelConfig()
    D = cfg.proto_dim
    strat = FedSTIL(cfg, n_clients=_C, epochs=2)
    # the server programs are built against the engine mesh; on one device
    # (what the fleet cell runs) Θ never crosses a shard boundary, so the
    # flatten emits f32 and no wire cast appears in the traces
    strat.mesh = engine_mesh(jax.devices()[:1])
    # tiny concrete states: _server_fns flattens an example theta eagerly,
    # and stack_states is the cheapest way to an exact opt-state / extras
    # structure. C is small here; the abstract args re-shape to _C.
    C0 = 4
    states = {c: strat.init_client(jax.random.PRNGKey(c)) for c in range(C0)}
    stacked = strat.stack_states(states)
    theta_example = strat.eval_theta_stacked(stacked)       # (C0, ...) pytree
    relevance, flatten, aggregate, unflatten = strat._server_fns(
        theta_example)
    P = int(np.sum([np.prod(l.shape[1:])
                    for l in jax.tree.leaves(theta_example)]))

    def _stretch(tree):                 # (C0, ...) SDS -> (_C, ...) SDS
        return jax.tree.map(lambda l: _SDS((_C,) + l.shape[1:], l.dtype),
                            tree)

    # the ring push takes the per-client validity mask plus the
    # per-client staleness counter it carries round-to-round
    ring_args = (_SDS((_C, _HIST, D), _F32), _SDS((_C, _HIST), _F32),
                 _SDS((_C,), _F32),
                 _SDS((_C, D), _F32), _SDS((_C,), _F32))

    register_runtime(
        "federated.fedstil_server_relevance", relevance,
        abstract_args=lambda: (ring_args, {}),
        module="repro.core.fedstil",
        oracle="repro.core.relevance.RelevanceTracker.relevance",
        carry=(0, 1, 2), donate=(0, 1, 2), budget_bytes=64 << 20)

    def server_round(buf, valid, stale, feats, mask, theta):
        """The full staged server round (FedSTIL ``server_round_stacked``
        data path) as one traceable program: ring push + Eq. 4/5
        relevance (with its rider telemetry mets), flatten, the fused
        Eq. 5→6 aggregate, unflatten, and the nz row mask."""
        buf, valid, stale, w_raw, mets = relevance(buf, valid, stale,
                                                   feats, mask)
        b_flat, wn = aggregate(w_raw, flatten(theta))
        nz = jnp.sum(wn, axis=1) > 0
        return buf, valid, stale, unflatten(b_flat), nz, mets

    register_runtime(
        "federated.fedstil_server_round", server_round,
        abstract_args=lambda: (
            ring_args + (_stretch(_sds_like(theta_example)),), {}),
        module="repro.core.fedstil",
        oracle="repro.core.fedstil.FedSTIL.server_round",
        # the shard_map'd aggregate holds the all-gathered (Cp, P) f32 Θ
        # next to the shard's input block (23 MB at C=100)
        carry=(0, 1, 2), donate=(0, 1, 2), budget_bytes=160 << 20)

    # the aggregate stage alone, at a wider P than the model's
    register_runtime(
        "federated.sharded_aggregate", aggregate,
        abstract_args=lambda: ((_SDS((_C, _C), _F32),
                                _SDS((_C, 4096), _F32)), {}),
        module="repro.core.fedstil",
        oracle="repro.kernels.ref.fused_relevance_aggregate_ref",
        budget_bytes=64 << 20)

    epochs, batch = strat.epochs, strat.batch
    register_runtime(
        "federated.stacked_local_train", strat._stacked_train_fn(),
        abstract_args=lambda: ((
            _stretch(_sds_like(stacked.trainable)),
            _stretch(_sds_like(stacked.opt_state)),
            _stretch(_sds_like(strat._stacked_loss_extras(stacked))),
            _SDS((_C, epochs, batch, D), _F32),
            _SDS((_C, epochs, batch), _I32)), {}),
        module="repro.federated.base",
        oracle="repro.federated.base.Strategy._run_epochs",
        # the static liveness estimate is deliberately conservative around
        # the vmap-of-scan autodiff (it keeps VJP residuals live across the
        # whole epoch scan); measured ~584 MB at C=100 on this estimator
        carry=(0, 1), donate=(0, 1), budget_bytes=640 << 20)

    # every client's nearest-mean exemplars at the fleet cell's task size
    # (144 train prototypes per client): adaptive forward + three sorts
    n_task = 144
    register_runtime(
        "federated.fedstil_exemplar_select", strat._exemplar_fn(),
        abstract_args=lambda: ((
            _stretch(_sds_like(theta_example)),
            _SDS((_C, n_task, D), _F32),
            _SDS((_C, n_task), _I32)), {}),
        module="repro.core.fedstil",
        oracle="repro.core.rehearsal.PrototypeMemory.add_task",
        budget_bytes=64 << 20)

    # the flatten stage rides along so the full staged-jit server structure
    # (see the ROADMAP note on why it is NOT one mega-jit) stays under
    # analysis
    register_runtime(
        "federated.fedstil_server_flatten", flatten,
        abstract_args=lambda: ((_stretch(_sds_like(theta_example)),), {}),
        module="repro.core.fedstil",
        oracle="repro.common.pytree.tree_flatten_stacked",
        budget_bytes=128 << 20)


def _register_comm() -> None:
    from repro.comm.batched import BatchedCodec
    from repro.comm.codec import make_codec

    P = 4096
    codec = BatchedCodec(make_codec("topk+int8"), P,
                         engine_mesh(jax.devices()[:1]))
    enc_args = (_SDS((_C, P), _F32),)
    buffers_sds = jax.eval_shape(codec._enc_sparse, *enc_args)[0]

    register_runtime(
        "comm.batched_encode", codec._enc_sparse,
        abstract_args=lambda: (enc_args, {}),
        module="repro.comm.batched",
        oracle="repro.comm.codec.PipelineCodec.encode",
        budget_bytes=32 << 20)
    register_runtime(
        "comm.batched_encode_keyframe", codec._enc_dense,
        abstract_args=lambda: (enc_args, {}),
        module="repro.comm.batched",
        oracle="repro.comm.codec.PipelineCodec.encode",
        budget_bytes=32 << 20)
    register_runtime(
        "comm.batched_decode", codec._dec_sparse,
        abstract_args=lambda: ((buffers_sds,), {}),
        module="repro.comm.batched",
        oracle="repro.comm.codec.PipelineCodec.decode",
        budget_bytes=32 << 20)


def _register_eval() -> None:
    # the engine's batched eval program (the launch/eval_round CLI is a
    # thin harness around it)
    from repro.federated.base import sharded_eval_fn

    mesh = engine_mesh(jax.devices()[:1])
    from repro.core.edge_model import EdgeModelConfig
    from repro.core import edge_model as EM
    cfg = EdgeModelConfig()
    th = jax.eval_shape(lambda k: EM.init_adaptive_layers(k, cfg),
                        jax.random.PRNGKey(0))
    C, T, Q, G = 8, 3, 16, 96
    th_sds = jax.tree.map(lambda l: _SDS((C,) + l.shape, l.dtype), th)
    register_runtime(
        "federated.sharded_eval",
        sharded_eval_fn(mesh, kernel_backend="ref"),
        abstract_args=lambda: ((th_sds,
                                _SDS((C, T, Q, cfg.proto_dim), _F32),
                                _SDS((C, T, Q), _I32),
                                _SDS((C, T), _F32),
                                _SDS((C, G, cfg.proto_dim), _F32),
                                _SDS((C, G), _I32),
                                _SDS((C, G), _F32)), {}),
        module="repro.federated.base",
        oracle="repro.federated.simulation._eval_round",
        budget_bytes=64 << 20)


_register_fedstil()
_register_comm()
_register_eval()
