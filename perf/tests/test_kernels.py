"""Operations and bytes per call at the cells' shapes, counted by hand."""
import pytest

from conftest import ROOT


def _cost(kernel, shapes):
    from perf.harness import load_module
    return load_module(ROOT / "perf" / "kernels" / f"{kernel}.py").cost(shapes)


def _fleet_shapes():
    from perf import harness
    from perf.systems import fleet
    spec = harness.load_spec("fleet_c100.rounds")
    return fleet.kernel_shapes(spec.config, spec.traffic)


GALLERY = {"C": 4, "B": 64, "F": 64, "D": 128, "H": 128, "G": 131072,
           "k": 10}


def test_fleet_round_shapes():
    s = _fleet_shapes()
    assert (s["C"], s["P"], s["K"], s["rows"], s["n_train"]) == (
        100, 57664, 21624, 96, 144)


@pytest.mark.parametrize("kernel,shapes,ops,nbytes", [
    ("fused_relevance_aggregate", None, 2 * 100 * 100 * 57664,
     8 * 100 * 100 + 8 * 100 * 57664),
    ("batched_quantize", None, 0,
     4 * 100 * 21624 + 100 * 21624 + 4 * 100 * 85),
    ("batched_int8_pairwise_dist", dict(GALLERY, mode="int8"),
     4_294_967_296, 33_554_432 + 4_194_304 + 65_536 + 134_217_728),
    ("batched_ivf_shortlist",
     dict(GALLERY, mode="ivf", nlist=512, bcap=384, nprobe=8),
     2 * 4 * 64 * 3072 * 64,
     4 * 64 * 3072 * 76 + 4 * 4 * 64 * 64 + 4 * 4 * 64 * 8
     + 8 * 4 * 64 * 3072),
    ("batched_pairwise_dist",
     {"C": 100, "eval_Q": 96, "eval_G": 9504, "eval_D": 64},
     2 * 100 * 96 * 9504 * 64,
     4 * 100 * 96 * 64 + 4 * 100 * 9504 * 64 + 4 * 100 * 96 * 9504),
])
def test_kernel_counts(kernel, shapes, ops, nbytes):
    got = _cost(kernel, shapes if shapes is not None else _fleet_shapes())
    assert got[:2] == (ops, nbytes)
    assert got[2] == "bf16_flops_per_s"


def test_kernels_not_in_the_cell_cost_nothing():
    assert _cost("batched_ivf_shortlist", dict(GALLERY, mode="int8")) is None
    assert _cost("batched_int8_pairwise_dist",
                 dict(GALLERY, mode="ivf")) is None
    assert _cost("batched_pairwise_dist", _fleet_shapes()) is None


def test_whole_step_operations():
    from perf.harness import load_module
    kdir = ROOT / "perf" / "kernels"
    fwd = 2 * (128 * 128 + 128 * 64 + 64 * 512)
    train_row = 2 * fwd + 2 * (128 * 64 + 64 * 512)
    want = (100 * 5 * 96 * train_row + 100 * 144 * 2 * (128 * 128 + 128 * 64)
            + 2 * 100 * 100 * 57664)
    got = load_module(kdir / "fleet_round.py").round_ops(_fleet_shapes())
    assert got == want == 16_803_276_800
    q = load_module(kdir / "gallery_query.py").query_ops
    feat = 2 * (128 * 128 + 128 * 64)
    assert q(dict(GALLERY, mode="int8")) == feat + 2 * 64 * 131072
    assert q(dict(GALLERY, mode="ivf", nlist=512, bcap=384, nprobe=8)) == (
        feat + 2 * 64 * (512 + 8 * 384))


def test_peaks_table_is_keyed_by_device_kind():
    from perf.metrics import _common
    pk = _common.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["int8_ops_per_s"] == 393e12
    assert pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        _common.peaks("cpu")
