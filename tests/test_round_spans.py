"""Child spans of the stacked round loop and the serving launch.

A stacked FedSTIL run under a tracer records every child span of the
round's phases once per round under its parent, with host<->device byte
counters equal to the bytes of the arrays moved (from their shapes), and
trains bit for bit what the same run trains untraced. A batcher step
under a tracer records admission, upload, launch, readback and
completion under the right parents.
"""
import jax
import numpy as np
import pytest

from repro.core import FedSTIL
from repro.core import edge_model as EM
from repro.core.edge_model import EdgeModelConfig
from repro.data import FederatedReIDBenchmark
from repro.federated import run_simulation
from repro.obs import trace as obs
from repro.serving import ContinuousBatcher, GalleryIndex, RetrievalEngine

C, ROUNDS, EPOCHS, BATCH = 3, 2, 2, 8
PER_IDENTITY = 2      # below the rows per identity: selection drops rows

# parent -> children recorded once under each instance of the parent
CHILDREN = {
    "round.gather": {"gather.sample", "gather.upload"},
    "round.local_train": {"local.train", "local.forward", "local.rehearsal",
                          "local.task_feature"},
    "round.encode": {"comm.flatten", "comm.unflatten"},
    "round.server": {"server.readback"},
}


@pytest.fixture(scope="module")
def bench():
    return FederatedReIDBenchmark(n_clients=C, n_tasks=1, n_identities=30,
                                  ids_per_task=6, samples_per_id=5, seed=3)


def _run(bench, trace):
    """Run the stacked engine; returns the final state on the host."""
    cfg = EdgeModelConfig(n_classes=bench.n_classes)
    strat = FedSTIL(cfg, n_clients=C, epochs=EPOCHS, batch=BATCH,
                    per_identity=PER_IDENTITY, codec="topk+int8", seed=5)
    seen = {}
    apply = strat.apply_dispatch_stacked

    def capture(stacked, dispatch):
        out = apply(stacked, dispatch)
        seen["state"] = jax.tree.map(
            np.asarray, (out.trainable, out.opt_state, out.extras))
        seen["memory"] = [(m.protos.copy(), m.labels.copy())
                          for m in out.host["memory"]]
        return out

    strat.apply_dispatch_stacked = capture
    res = run_simulation(strat, bench, rounds=ROUNDS, eval_every=ROUNDS,
                         engine="stacked", trace=trace)
    return seen, res


@pytest.fixture(scope="module")
def traced(bench):
    tracer = obs.Tracer()
    seen, res = _run(bench, tracer)
    return tracer, seen, res


def _spans(tracer):
    return [e for e in tracer.events if e["kind"] == "span"]


def _round_of(spans):
    """Span id -> the round of its nearest ancestor (itself included)
    that carries one."""
    by_id = {e["id"]: e for e in spans}

    def rnd(e):
        while "round" not in e:
            e = by_id[e["parent"]]
        return e["round"]
    return {e["id"]: rnd(e) for e in spans}


def test_fleet_child_spans_once_per_round_under_their_parent(traced):
    tracer, _, _ = traced
    spans = _spans(tracer)
    for parent, children in CHILDREN.items():
        instances = [e for e in spans if e["name"] == parent]
        per_round = 2 if parent == "round.encode" else 1   # c2s and s2c
        assert len(instances) == ROUNDS * per_round, parent
        for inst in instances:
            kids = [e["name"] for e in spans if e["parent"] == inst["id"]]
            for child in children:
                assert kids.count(child) == 1, (parent, kids)
    # the nz readback after the server round sits at the top level
    top = [e for e in spans if e["name"] == "server.readback"
           and e["parent"] is None]
    assert sorted(e["round"] for e in top) == list(range(ROUNDS))


def test_declared_bytes_equal_the_arrays_moved(traced, bench):
    tracer, _, _ = traced
    cfg = EdgeModelConfig(n_classes=bench.n_classes)
    N = bench.task(0, 0).train_x.shape[0]
    D = cfg.proto_dim
    spans = _spans(tracer)
    rnd = _round_of(spans)

    def named(name):
        return [e for e in spans if e["name"] == name]

    def rows(r):
        # round 0 has no rehearsal pool yet; later rounds add B // 2 rows
        return min(BATCH, N) + (BATCH // 2 if r else 0)

    for e in named("gather.upload"):
        assert e["h2d_bytes"] == C * EPOCHS * rows(rnd[e["id"]]) * (D + 1) * 4
    for e in named("gather.sample"):
        assert e["rows"] == C * EPOCHS * rows(rnd[e["id"]])
    # prototypes and labels up, each client's exemplar order and count back
    for e in named("local.forward"):
        assert e["h2d_bytes"] == C * N * (D + 1) * 4
        assert e["d2h_bytes"] == C * (N + 1) * 4
    # the kept rows: every row of an identity with at most per_identity
    kept = sum(np.minimum(np.unique(bench.task(c, 0).train_y,
                                    return_counts=True)[1], PER_IDENTITY).sum()
               for c in range(C))
    for e in named("local.rehearsal"):
        assert e["rows"] == kept
    for e in named("local.task_feature"):
        assert e["h2d_bytes"] == C * D * 4
    for e in named("server.readback"):
        if e["parent"] is None:
            assert e["d2h_bytes"] == C            # nz: (C,) bool
        else:
            assert e["d2h_bytes"] == C * C * 4    # last_W: (C, C) f32
    moved = sum(e.get("h2d_bytes", 0) + e.get("d2h_bytes", 0)
                for e in spans if rnd[e["id"]] == 1)
    assert moved == (C * EPOCHS * rows(1) * (D + 1) * 4
                     + C * N * (D + 1) * 4 + C * (N + 1) * 4
                     + C * D * 4 + C * C * 4 + C)


def test_trained_state_bit_equal_with_tracer_on_and_off(traced, bench):
    _, on, res_on = traced
    off, res_off = _run(bench, None)
    for a, b in zip(jax.tree.leaves(on["state"]),
                    jax.tree.leaves(off["state"])):
        np.testing.assert_array_equal(a, b)
    for (pa, la), (pb, lb) in zip(on["memory"], off["memory"]):
        np.testing.assert_array_equal(pa, pb)
        np.testing.assert_array_equal(la, lb)
    assert res_on.rounds == res_off.rounds
    assert res_on.comm.total_c2s == res_off.comm.total_c2s


def _batcher(**kw):
    rng = np.random.default_rng(0)
    cfg = EdgeModelConfig()
    n_cam, G = 2, 40
    index = GalleryIndex(
        [rng.standard_normal((G, cfg.proto_dim)).astype(np.float32)
         for _ in range(n_cam)],
        [rng.integers(0, 12, G).astype(np.int32) for _ in range(n_cam)],
        capacity=G, keep_fp32=False)
    keys = jax.random.split(jax.random.PRNGKey(0), n_cam)
    thetas = [EM.init_adaptive_layers(k, cfg) for k in keys]
    theta = jax.tree.map(lambda *xs: np.stack(xs), *thetas)
    engine = RetrievalEngine(index, theta, k=5, mode="int8")
    return ContinuousBatcher(engine, batch=4, **kw), rng, cfg


@pytest.mark.parametrize("kw,slots", [({}, 4),
                                      ({"policy": "drr", "step_budget": 3},
                                       3)])
def test_batcher_step_spans_under_their_parents(kw, slots):
    batcher, rng, cfg = _batcher(**kw)
    for client in (0, 0, 0, 1):
        batcher.submit(client, rng.standard_normal(cfg.proto_dim))
    depth = batcher.pending
    tracer = obs.Tracer()
    with obs.active(tracer):
        done = batcher.step()
    assert len(done) == slots
    spans = {e["name"]: e for e in _spans(tracer)}
    assert set(spans) == {"serve.admit", "serve.batch", "serve.upload",
                          "serve.launch", "serve.readback", "serve.complete"}
    assert spans["serve.admit"]["depth"] == depth == 4
    assert spans["serve.admit"]["slots"] == slots
    assert spans["serve.complete"]["slots"] == slots
    batch = spans["serve.batch"]
    for top in ("serve.admit", "serve.batch", "serve.complete"):
        assert spans[top]["parent"] is None
    for child in ("serve.upload", "serve.launch", "serve.readback"):
        assert spans[child]["parent"] == batch["id"]
    n_cam, B = batcher._qmask.shape
    Dp = batcher._qp.shape[-1]
    assert spans["serve.upload"]["h2d_bytes"] == n_cam * B * (Dp + 1) * 4
    # G = 40 rows: too few blocks, one top-k over every row
    assert spans["serve.launch"]["topk_rows"] == 40
    # (C, B, k) int32 ids + float32 distances
    assert spans["serve.readback"]["d2h_bytes"] == n_cam * B * 5 * 8
