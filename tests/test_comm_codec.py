"""Wire-format codec subsystem tests: stage round-trips (exact for
lossless, bounded + deterministic for lossy), host-vs-batched parity,
measured-vs-formula accounting, the FedWeIT sparse-bytes formula fix, and
the end-to-end fidelity guard (codec-on FedSTIL within tolerance of the
uncompressed run at under half the dense-FedAvg payload)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm.accounting import CommLog
from repro.comm.batched import BatchedCodec
from repro.comm.codec import (grouped_topk_select_host,
                              make_codec, quantize_host, topk_select_host)
from repro.core import FedSTIL
from repro.core.edge_model import EdgeModelConfig
from repro.data import FederatedReIDBenchmark
from repro.federated import FedAvg, run_simulation
from repro.sharding.specs import engine_mesh


def _tree(rng, scale=1.0):
    return {"a": {"w": rng.standard_normal((13, 7)).astype(np.float32) * scale,
                  "b": rng.standard_normal((7,)).astype(np.float32)},
            "c": rng.standard_normal((41,)).astype(np.float32)}


# ---- lossless stages --------------------------------------------------------

def test_raw_roundtrip_exact():
    rng = np.random.default_rng(0)
    tree = _tree(rng)
    codec = make_codec("raw")
    payload = codec.encode(tree)
    dec = codec.decode(payload)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(dec)):
        np.testing.assert_array_equal(a, b)
        assert a.dtype == b.dtype and a.shape == b.shape
    assert payload.nbytes == sum(l.nbytes for l in jax.tree.leaves(tree))


def test_delta_raw_stream_reconstructs():
    """delta+raw over a drifting stream: every round reconstructs the
    current payload (residual + reference is exact in fp32 up to the
    subtract/add round-trip)."""
    rng = np.random.default_rng(1)
    codec = make_codec("delta")
    base = rng.standard_normal(257).astype(np.float32)
    for r in range(4):
        tree = {"w": base + 0.1 * r}
        dec = codec.decode(codec.encode(tree, peer=0), peer=0)
        np.testing.assert_allclose(dec["w"], tree["w"], atol=1e-6, rtol=0)


# ---- lossy stages: bounded error + determinism ------------------------------

def test_int8_error_bound_and_determinism():
    rng = np.random.default_rng(2)
    tree = _tree(rng, scale=3.0)
    codec = make_codec("int8", chunk=16)
    p1 = codec.encode(tree)
    p2 = codec.encode(tree)
    for k in p1.buffers:
        np.testing.assert_array_equal(p1.buffers[k], p2.buffers[k])
    dec = codec.decode(p1)
    flat = np.concatenate([l.ravel() for l in jax.tree.leaves(tree)])
    rec = np.concatenate([l.ravel() for l in jax.tree.leaves(dec)])
    err = np.abs(flat - rec)
    # per-chunk scale = chunk absmax/127, round-to-nearest: err <= scale/2
    for o in range(0, flat.size, 16):
        chunk = flat[o:o + 16]
        bound = np.abs(chunk).max() / 127.0 * 0.5 + 1e-7
        assert err[o:o + 16].max() <= bound


def test_bf16_roundtrip_bound():
    rng = np.random.default_rng(3)
    tree = _tree(rng)
    codec = make_codec("bf16")
    payload = codec.encode(tree)
    assert payload.nbytes == sum(l.size * 2 + 0 for l in jax.tree.leaves(tree))
    dec = codec.decode(payload)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(dec)):
        np.testing.assert_allclose(a, b, rtol=1e-2, atol=1e-6)


def test_grouped_topk_invariants():
    """Grouped selection keeps exactly kg per group, the kg largest
    magnitudes, ties by lowest index, deterministically."""
    rng = np.random.default_rng(4)
    x = rng.standard_normal(80).astype(np.float32)
    x[8:16] = 1.0                       # a full group of exact ties
    v1, i1 = grouped_topk_select_host(x, 8, 3)
    v2, i2 = grouped_topk_select_host(x, 8, 3)
    np.testing.assert_array_equal(v1, v2)
    np.testing.assert_array_equal(i1, i2)
    assert len(v1) == 80 // 8 * 3
    for b in range(10):
        grp = np.abs(x[b * 8:(b + 1) * 8])
        kept = sorted(i1[(i1 >= b * 8) & (i1 < (b + 1) * 8)] - b * 8)
        order = np.argsort(-grp, kind="stable")[:3]     # ties: lowest index
        assert kept == sorted(order), (b, kept, order)
    # the tie group keeps its first three indices
    assert sorted(i1[(i1 >= 8) & (i1 < 16)]) == [8, 9, 10]


def test_global_topk_tie_semantics():
    """Exact global top-k: entries strictly above the threshold always
    survive; ties at the threshold are kept by lowest index."""
    x = np.array([1.0, 1.0, 1.0, 5.0], np.float32)
    vals, idx = topk_select_host(x, 2)
    assert 3 in idx                     # the 5 must survive the tie pile
    assert list(idx) == [0, 3]
    vals, idx = topk_select_host(x, 3)
    assert list(idx) == [0, 1, 3]


def test_topk_codec_reconstruction_and_keyframe():
    """topk+int8 (delta default ON): the first payload is a dense
    keyframe; later payloads are sparse residuals whose reconstruction
    error shrinks on a static stream."""
    rng = np.random.default_rng(5)
    tree = {"w": rng.standard_normal(4096).astype(np.float32)}
    codec = make_codec("topk+int8")
    p0 = codec.encode(tree, peer=0)
    assert "idx_bits" not in p0.buffers         # keyframe ships dense
    d0 = codec.decode(p0, peer=0)
    p1 = codec.encode(tree, peer=0)
    assert "idx_bits" in p1.buffers             # residuals ship sparse
    d1 = codec.decode(p1, peer=0)
    e0 = np.abs(d0["w"] - tree["w"]).max()
    e1 = np.abs(d1["w"] - tree["w"]).max()
    assert e1 <= e0 + 1e-7
    # grouped indices ship bit-packed: 3 bits per kept slot at group=8
    k = p1.schema["k"]
    assert p1.buffers["idx_bits"].dtype == np.uint8
    assert p1.buffers["idx_bits"].nbytes == 3 * ((k + 7) // 8)
    # stateless variant: sparse from the first payload
    stateless = make_codec("topk+int8", delta=False)
    ps = stateless.encode(tree, peer=0)
    assert "idx_bits" in ps.buffers
    dec = stateless.decode(ps, peer=0)
    kept = dec["w"] != 0
    assert kept.sum() == ps.schema["k"]


# ---- host vs batched parity -------------------------------------------------

@pytest.mark.parametrize("spec", ["int8", "topk+int8", "topk"])
def test_host_vs_batched_parity(spec):
    """The numpy host codec and the jitted batched device program are the
    same codec: identical wire bytes and bit-identical reconstructions
    (including over a delta stream with its keyframe)."""
    rng = np.random.default_rng(6)
    C, P = 4, 999
    host = make_codec(spec)
    batched = BatchedCodec(make_codec(spec), P,
                           engine_mesh(jax.devices()[:1]))
    for r in range(3):
        mat = rng.standard_normal((C, P)).astype(np.float32) * (1 + r)
        buffers = batched.encode(jnp.asarray(mat))
        dec_b = np.asarray(batched.decode(buffers))
        per_client = batched.per_client_bytes(buffers)
        for c in range(C):
            payload = host.encode({"w": mat[c]}, peer=c)
            assert payload.nbytes == per_client
            dec_h = host.decode(payload, peer=c)["w"]
            np.testing.assert_allclose(dec_h, dec_b[c], atol=1e-6, rtol=0)


def test_batched_rejects_global_topk():
    with pytest.raises(ValueError):
        BatchedCodec(make_codec("topk", k=10), 100,
                     engine_mesh(jax.devices()[:1]))


# ---- accounting: measured vs formula ---------------------------------------

def test_commlog_measured_vs_formula():
    log = CommLog()
    log.log_c2s(0, 1000)
    assert not log.measured
    log.log_c2s(1, 1000, measured=300)
    log.log_s2c_many(1, 500, 3, measured=100)
    assert log.measured
    assert log.total_c2s == 1300 and log.total_c2s_formula == 2000
    assert log.total_s2c == 300 and log.total_s2c_formula == 1500
    rows = log.round_breakdown()
    assert rows[1] == {"round": 1, "c2s_wire": 300, "s2c_wire": 300,
                       "c2s_formula": 1000, "s2c_formula": 1500}


def test_fedweit_sparse_bytes_matches_measured():
    """Satellite fix: FedWeIT's formula counts the ACTUAL nonzeros of the
    sparsified A (ties at the top-k threshold keep > k entries), and that
    formula equals the measured bytes of a lossless sparse encoding."""
    cfg = EdgeModelConfig(n_classes=16)
    from repro.federated import FedWeIT
    s = FedWeIT(cfg, n_clients=3)
    rng = np.random.default_rng(7)
    A = {"l1": {"w": rng.standard_normal((32, 16)).astype(np.float32)}}
    # force ties at the threshold: duplicate the k-th magnitude
    flat = A["l1"]["w"].ravel()
    flat[:5] = 0.5
    A_sp = s._sparsify(A)
    nnz = int(sum(np.count_nonzero(np.asarray(l))
                  for l in jax.tree.leaves(A_sp)))
    total = sum(l.size for l in jax.tree.leaves(A_sp))
    formula = s.sparse_bytes(A_sp)
    assert formula == nnz * 8
    # ties can keep more than the closed-form k = total * keep_frac
    assert nnz >= int(total * 0.3)
    # measured: lossless global top-nnz encoding of the sparse tree picks
    # exactly the nonzeros -> values (4B) + indices (4B) per kept entry
    codec = make_codec("topk", k=nnz, delta=False)
    payload = codec.encode(A_sp)
    assert payload.nbytes == formula
    dec = codec.decode(payload)
    np.testing.assert_array_equal(dec["l1"]["w"],
                                  np.asarray(A_sp["l1"]["w"]))


# ---- end-to-end fidelity guard (tier-1) ------------------------------------

@pytest.fixture(scope="module")
def bench():
    return FederatedReIDBenchmark(n_clients=3, n_tasks=3, n_identities=60,
                                  ids_per_task=10, samples_per_id=8, seed=1)


def test_fedstil_codec_fidelity_guard(bench):
    """FedSTIL with the default wire codec stays within tolerance of the
    uncompressed run while moving < half the dense FedAvg payload.

    The mAP guard compares means over three seeds: a single run's coded-
    minus-uncompressed gap is dominated by the seed (measured over seeds
    0-5: -0.017 to +0.060), so one seed says more about the draw than
    about the codec."""
    cfg = EdgeModelConfig(n_classes=bench.n_classes)
    seeds = (0, 1, 2)
    base = [run_simulation(FedSTIL(cfg, n_clients=3, epochs=3, seed=s),
                           bench, rounds=6, eval_every=3, seed=s)
            for s in seeds]
    runs = [run_simulation(
        FedSTIL(cfg, n_clients=3, epochs=3, codec="topk+int8", seed=s),
        bench, rounds=6, eval_every=3, seed=s) for s in seeds]
    coded = runs[0]
    avg = run_simulation(FedAvg(cfg, epochs=3), bench, rounds=6, eval_every=3)
    assert coded.comm.measured
    assert (np.mean([r.final("mAP") for r in runs])
            >= np.mean([r.final("mAP") for r in base]) - 0.03)
    # measured wire strictly below dense FedAvg, and >= 50% below
    assert coded.comm.total < 0.5 * avg.comm.total
    # formulas keep reporting the dense payload as the cross-check oracle
    assert coded.comm.total < coded.comm.total_formula
    rows = coded.comm_breakdown()
    assert rows and all(r["c2s_wire"] <= r["c2s_formula"] for r in rows)


def test_stacked_engine_codec_matches_host(bench):
    """Both engines run the same wire codec: same measured bytes (up to
    the stacked engine's per-client nz bitmap) and metrics in tolerance.

    Byte parity holds because this bench dispatches to every client from
    round 0 (nz all-true); under partial nz the stacked engine's broadcast
    wire model deliberately counts all C rows (see simulation.py)."""
    cfg = EdgeModelConfig(n_classes=bench.n_classes)
    host = run_simulation(
        FedSTIL(cfg, n_clients=3, epochs=2, codec="topk+int8"), bench,
        rounds=4, eval_every=2)
    stacked = run_simulation(
        FedSTIL(cfg, n_clients=3, epochs=2, codec="topk+int8"), bench,
        rounds=4, eval_every=2, engine="stacked")
    assert abs(stacked.comm.total - host.comm.total) <= 4 * 3  # nz bytes
    assert abs(stacked.final("mAP") - host.final("mAP")) < 0.02


def test_quantize_host_zero_chunk():
    q, s = quantize_host(np.zeros(10, np.float32), 4)
    assert (q == 0).all() and (s == 1.0).all()


def test_make_codec_rejects_unknown():
    with pytest.raises(ValueError):
        make_codec("topk+gzip")
    with pytest.raises(ValueError):
        make_codec("int8+bf16")
    assert make_codec(None) is None


def test_fedweit_codec_keeps_counters_out_of_wire(bench):
    """FedWeIT's A_nnz/neighbors_nnz accounting counters ship verbatim:
    a large integer must never share a quantization chunk with A entries
    (it would inflate the chunk scale ~50x). The sim must run and report
    measured < formula."""
    from repro.federated import FedWeIT
    cfg = EdgeModelConfig(n_classes=bench.n_classes)
    res = run_simulation(FedWeIT(cfg, epochs=2, n_clients=3, codec="int8"),
                         bench, rounds=2, eval_every=2)
    assert res.comm.measured
    assert res.comm.total < res.comm.total_formula
    assert np.isfinite(res.final("mAP"))


def test_simulation_codec_int8_fedavg(bench):
    """A non-FedSTIL strategy picks up codecs through the same hooks:
    int8 wire ~ 1/4 the formula bytes, measured flag set."""
    cfg = EdgeModelConfig(n_classes=bench.n_classes)
    res = run_simulation(FedAvg(cfg, epochs=2, codec="int8"), bench,
                         rounds=2, eval_every=2)
    assert res.comm.measured
    assert res.comm.total < 0.30 * res.comm.total_formula
    assert np.isfinite(res.final("mAP"))