"""The stacked engine's exemplar selection against the host oracle.

``FedSTIL._exemplar_fn`` picks every client's nearest-mean exemplars in
one jitted program (adaptive forward + ``select_exemplars``). Its kept
rows, in order, equal what ``PrototypeMemory.add_task`` stores from the
same forward outputs; and a stacked run's per-client memories, eviction
included, equal C host memories fed those outputs round by round.
"""
import jax
import numpy as np
import pytest

from repro.core import FedSTIL
from repro.core import edge_model as EM
from repro.core.edge_model import EdgeModelConfig
from repro.core.rehearsal import (PrototypeMemory, numpy_row_sum,
                                  select_exemplars)
from repro.data import FederatedReIDBenchmark
from repro.federated import run_simulation

CFG = EdgeModelConfig(proto_dim=16, hidden=16, feat_dim=8, n_classes=32)
PER_IDENTITY = 4


def _fewer_and_more(rng, C, N):
    # identity 0 has 2 rows (< per_identity, all kept), 1 has 6, 2 the rest
    return np.tile(np.repeat([0, 1, 2], [2, 6, N - 8]), (C, 1))


def _two_row_identities(rng, C, N):
    # a pair's two distances are equal in exact arithmetic: rounding
    # alone orders them, so the device must round as numpy does
    return np.tile(np.repeat(np.arange(N // 2), 2), (C, 1))


def _unsorted_sparse(rng, C, N):
    return rng.choice([905, 17, 3, 4242, 61], size=(C, N))


def _single_identity(rng, C, N):
    return np.full((C, N), 42)


def _mixed_label_sets(rng, C, N):
    sets = [[1, 2], [7, 300, 12, 5], [9], [100, 4, 8, 2, 6, 11, 13]]
    return np.stack([rng.choice(sets[c % len(sets)], size=N)
                     for c in range(C)])


CASES = {"fewer_and_more": _fewer_and_more,
         "unsorted_sparse": _unsorted_sparse,
         "single_identity": _single_identity,
         "mixed_label_sets": _mixed_label_sets,
         "two_row_identities": _two_row_identities}


def _theta(C):
    keys = jax.random.split(jax.random.PRNGKey(0), C)
    return jax.vmap(lambda k: EM.init_adaptive_layers(k, CFG))(keys)


@pytest.mark.parametrize("case", sorted(CASES))
def test_selection_program_matches_add_task(case):
    C, N = 8, 24
    rng = np.random.default_rng(sorted(CASES).index(case))
    protos = rng.standard_normal((C, N, CFG.proto_dim)).astype(np.float32)
    labels = CASES[case](rng, C, N)
    theta = _theta(C)
    strat = FedSTIL(CFG, n_clients=C, per_identity=PER_IDENTITY)
    order, count = jax.device_get(strat._exemplar_fn()(theta, protos,
                                                       labels))
    outputs = np.asarray(jax.jit(jax.vmap(
        lambda th, p: EM.adaptive_forward(th, p)[0]))(theta, protos))
    for c in range(C):
        assert sorted(order[c].tolist()) == list(range(N))   # a permutation
        host = PrototypeMemory(capacity=10 ** 6, per_identity=PER_IDENTITY)
        host.add_task(protos[c], labels[c], outputs[c], task_id=0)
        dev = PrototypeMemory(capacity=10 ** 6, per_identity=PER_IDENTITY)
        dev.add_selected(protos[c], labels[c], order[c, :count[c]],
                         task_id=0)
        np.testing.assert_array_equal(dev.protos, host.protos)
        np.testing.assert_array_equal(dev.labels, host.labels)
        np.testing.assert_array_equal(dev.task_ids, host.task_ids)


def test_selection_breaks_exact_ties_by_row_index():
    rng = np.random.default_rng(7)
    N, F = 12, 3
    outputs = rng.standard_normal((1, N, F)).astype(np.float32)
    outputs[0, 1::2] = outputs[0, 0::2]       # every distance tied in pairs
    labels = np.zeros((1, N), np.int32)
    order, count = jax.jit(select_exemplars, static_argnums=2)(
        outputs, labels, 5)
    want = PrototypeMemory(capacity=N, per_identity=5).select(labels[0],
                                                               outputs[0])
    np.testing.assert_array_equal(np.asarray(order[0, :count[0]]), want)
    assert int(count[0]) == 5


@pytest.mark.parametrize("width", [3, 8, 64, 100, 300])
def test_numpy_row_sum_has_numpy_bits(width):
    """Under jit: a sum in another order would differ from numpy's in the
    last bit."""
    rng = np.random.default_rng(width)
    x = (rng.standard_normal((200, width))
         * rng.uniform(0.1, 10.0, (200, 1))).astype(np.float32)
    got = jax.jit(numpy_row_sum)(x * x)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.add.reduce(x * x, axis=1))


def test_stacked_memories_equal_host_memories_fed_the_same_outputs():
    C, rounds = 3, 5
    bench = FederatedReIDBenchmark(n_clients=C, n_tasks=2, n_identities=40,
                                   ids_per_task=8, samples_per_id=6, seed=2)
    cfg = EdgeModelConfig(n_classes=bench.n_classes)
    # 8 identities x 2 kept rows a round against a capacity of 40: the
    # third round starts evicting the oldest round's exemplars
    strat = FedSTIL(cfg, n_clients=C, epochs=2, memory_size=40,
                    per_identity=2)
    host = [PrototypeMemory(capacity=40, per_identity=2) for _ in range(C)]
    forward = jax.jit(jax.vmap(lambda th, p: EM.adaptive_forward(th, p)[0]))
    train = strat.local_train_stacked
    evicted = []

    def local_train_stacked(stacked, bx, by, protos_list, labels_list, rnd):
        stacked, upload = train(stacked, bx, by, protos_list, labels_list,
                                rnd)
        outputs = np.asarray(forward(upload["theta"], np.stack(protos_list)))
        for c, mem in enumerate(host):
            mem.add_task(protos_list[c], labels_list[c], outputs[c],
                         task_id=rnd)
        evicted.append(host[0].task_ids.min() > 0)
        for dev, ref in zip(stacked.host["memory"], host):
            np.testing.assert_array_equal(dev.protos, ref.protos)
            np.testing.assert_array_equal(dev.labels, ref.labels)
            np.testing.assert_array_equal(dev.task_ids, ref.task_ids)
        return stacked, upload

    strat.local_train_stacked = local_train_stacked
    run_simulation(strat, bench, rounds=rounds, eval_every=rounds,
                   engine="stacked")
    assert len(evicted) == rounds and any(evicted)
