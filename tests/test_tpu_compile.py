"""Compile the main-path Pallas kernels for a TPU v5e at real widths.

No chip is needed: the installed TPU compiler targets a described v5e:2x2
topology, and ``.compile()`` raises what the chip's compiler would raise
(block shapes off the (8, 128) tiling, scoped-VMEM overruns, kernels the
partitioner cannot split). Nothing runs, so these say nothing about
values — the interpret-mode kernel tests and ``chip_smoke.py`` do.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

# the kernel modules (the package re-exports same-named dispatchers)
QZ, TK, I8, IVF, PD, RA, KL = (
    importlib.import_module(f"repro.kernels.{m}") for m in (
        "quantize", "topk_pack", "int8_dist", "ivf", "pairwise_dist",
        "relevance_aggregate", "kl_similarity"))

C, P = 100, 57664            # FedSTIL clients x EdgeModelConfig() params
K = (P // 8) * 2             # grouped top-k slots at group=8, kg=2
G, F = 131072, 64            # serving rows per client x feature width


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                     # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


CASES = {
    "fused_relevance_aggregate": (
        lambda w, t: RA.fused_relevance_aggregate(w, t, interpret=False),
        [((C, C),), ((C, P),)]),
    "batched_quantize": (
        lambda x: QZ.batched_quantize(x, interpret=False), [((C, P),)]),
    "batched_quantize_sparse": (
        lambda x: QZ.batched_quantize(x, interpret=False), [((C, K),)]),
    "batched_quantize_index_rows": (     # the serving refresh: chunk = F
        lambda x: QZ.batched_quantize(x, chunk=F, interpret=False),
        [((4, G * F),)]),
    "batched_dequantize": (
        lambda q, s: QZ.batched_dequantize(q, s, interpret=False),
        [((C, P), jnp.int8), ((C, -(-P // 256)),)]),
    "batched_topk_pack": (
        lambda x: TK.batched_topk_pack(x, kg=2, interpret=False),
        [((C, P),)]),
    "batched_topk_unpack": (
        lambda v, i: TK.batched_topk_unpack(v, i, p=P, kg=2,
                                            interpret=False),
        [((C, K),), ((C, K), jnp.int32)]),
    "batched_idx_bitpack": (
        lambda i: TK.batched_idx_bitpack(i, kg=2, interpret=False),
        [((C, K), jnp.int32)]),
    "batched_idx_bitunpack": (
        lambda p: TK.batched_idx_bitunpack(p, k=K, kg=2, interpret=False),
        [((C, 3 * (K // 8)), jnp.uint8)]),
    "batched_pairwise_dist_eval": (       # C=100 x 2 tasks x 96 queries
        lambda q, g: PD.batched_pairwise_dist(q, g, interpret=False),
        [((C, 192, F),), ((C, 19008, F),)]),
    "batched_int8_pairwise_dist": (
        lambda q, g, s, n: I8.batched_int8_pairwise_dist(
            q, g, s, n, interpret=False),
        [((4, 64, F),), ((4, G, F), jnp.int8), ((4, G),), ((4, G),)]),
    "batched_cluster_dist": (
        lambda q, c, n: IVF.batched_cluster_dist(q, c, n, interpret=False),
        [((4, 64, F),), ((4, 512, F),), ((4, 512),)]),
    "batched_ivf_shortlist_scores": (
        lambda q, p, b, k: IVF.batched_ivf_shortlist_scores(
            q, p, b, k, interpret=False),
        [((4, 64, F),), ((4, 64, 8), jnp.int32),
         ((4, 512, 384, F), jnp.int8), ((4, 512, 3, 384),)]),
    "kl_similarity": (
        lambda a, b: KL.kl_similarity(a, b, interpret=False),
        [((C, 128),), ((C * 6, 128),)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = CASES[name]
    sds = [_sds(one_chip, *a) for a in args]
    compiled = jax.jit(fn).lower(*sds).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_aggregate_compiles_on_v5e_2x2(topo):
    """The engine's shard_map'd Eq. 5→6 aggregate over a 4-chip mesh: Θ
    is all-gathered across the chips and the fused kernel runs per shard
    on its own relevance rows."""
    from repro.core.fedstil import sharded_aggregate_fn
    from repro.sharding.specs import engine_mesh, stacked_aggregate_specs

    mesh = engine_mesh(topo.devices)
    sp = stacked_aggregate_specs()
    fn = sharded_aggregate_fn(mesh, backend="pallas")
    compiled = fn.lower(_sds(NamedSharding(mesh, sp["w"]), (C, C)),
                        _sds(NamedSharding(mesh, sp["thetas"]),
                             (C, P))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text


def test_client_step_gradients_are_not_compiler_reductions(one_chip):
    """The vmapped FedSTIL client step, compiled for a v5e, holds no
    reduction made by autodiff: the compiler lays those out by the client
    count, which made 25 clients per chip and 100 on one chip round the
    same client's gradient differently. Biases, BN, the class sums and the
    clip norm sum through ``common.precision`` in a fixed order instead."""
    import re

    import numpy as np

    from repro.core import FedSTIL
    from repro.core.edge_model import EdgeModelConfig

    cfg = EdgeModelConfig()
    n = 4
    strat = FedSTIL(cfg, n_clients=n)
    keys = jax.random.split(jax.random.PRNGKey(0), n)
    stacked = strat.stack_states({c: strat.init_client(keys[c])
                                  for c in range(n)})
    args = (stacked.trainable, stacked.opt_state,
            strat._stacked_loss_extras(stacked),
            np.zeros((n, 2, strat.batch, cfg.proto_dim), np.float32),
            np.zeros((n, 2, strat.batch), np.int32))
    sds = jax.tree.map(lambda l: _sds(one_chip, l.shape, l.dtype), args)
    text = strat._stacked_train_fn().lower(*sds).compile().as_text()
    grads = [line for line in text.splitlines()
             if re.search(r"= f32\[[^\]]*\]\S* reduce\(", line)
             and "transpose(" in line]
    assert not grads, grads[:3]


def test_exemplar_selection_compiles_for_v5e(one_chip):
    """The stacked engine's exemplar program at the fleet's size (100
    clients x 144 prototypes): forward, the identity loop and three sorts
    on one chip, with only the (C, N) order and (C,) count as outputs."""
    from repro.core import FedSTIL
    from repro.core import edge_model as EM
    from repro.core.edge_model import EdgeModelConfig

    cfg, n = EdgeModelConfig(), 144
    theta = jax.eval_shape(lambda k: jax.vmap(
        lambda kk: EM.init_adaptive_layers(kk, cfg))(jax.random.split(k, C)),
        jax.random.PRNGKey(0))
    theta = jax.tree.map(lambda l: _sds(one_chip, l.shape, l.dtype), theta)
    compiled = FedSTIL(cfg, n_clients=C)._exemplar_fn().lower(
        theta, _sds(one_chip, (C, n, cfg.proto_dim)),
        _sds(one_chip, (C, n), jnp.int32)).compile()
    assert compiled.as_text().count(" sort(") == 3
    assert compiled.memory_analysis().output_size_in_bytes < C * (n + 1) * 8


def test_round_gather_compiles_for_v5e(topo):
    """The stacked engine's round gather at the fleet's size (100 clients
    x 144 task rows, 5 epochs of 64 task + 32 pool rows) on a one-chip
    mesh: every client's rows, draws and pool sit on its own shard, so
    the program holds no collective."""
    from repro.core import FedSTIL
    from repro.core.edge_model import EdgeModelConfig
    from repro.sharding import specs

    cfg, n, E, B = EdgeModelConfig(), 144, 5, 64
    D, i32 = cfg.proto_dim, jnp.int32
    strat = FedSTIL(cfg, n_clients=C, epochs=E, batch=B)
    strat.mesh = specs.engine_mesh(topo.devices[:1])
    rows = NamedSharding(strat.mesh, specs.client_row_spec(1))
    compiled = strat._gather_fn().lower((
        _sds(rows, (C, n, D)), _sds(rows, (C, n), i32),
        _sds(rows, (C, E, B), i32), _sds(rows, (C, B, D)),
        _sds(rows, (C, B), i32), _sds(rows, (C, E, B // 2), i32))).compile()
    text = compiled.as_text()
    for op in ("all-gather", "all-reduce", "all-to-all",
               "collective-permute"):
        assert op not in text, op
    assert " gather(" in text
    out = C * E * (B + B // 2) * (D + 1) * 4
    assert compiled.memory_analysis().output_size_in_bytes >= out
