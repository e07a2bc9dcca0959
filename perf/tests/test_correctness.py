"""``correct`` on the CPU at test size: the program as it is passes; the
control (the reference one precision below the configuration's) and each
fault the cell can have, planted in the timed path, fail."""
import numpy as np
import pytest


def _judge(spec, readings):
    from perf import harness
    return all(v[3] for v in harness.judge(readings, spec.limits))


@pytest.mark.parametrize("cell", ["fleet_c100.rounds",
                                  "gallery_c4_g131k.exact_steady",
                                  "gallery_c4_g131k.ivf_steady"])
def test_program_is_correct(cell, tiny, drive):
    out, _ = drive(tiny(cell))
    assert out["correct"], out["checks"]


def test_fleet_control_fails(tiny):
    from perf import calibrate
    spec = tiny("fleet_c100.rounds")
    got = list(calibrate.fleet_controls(spec, [2 ** 31 + 7]))
    for who in ("control", "half_batch"):
        reading = next(r for r in got if r["who"] == who)
        assert not _judge(spec, reading), reading


def test_gallery_control_fails(tiny):
    from perf import calibrate
    for cell in ("gallery_c4_g131k.exact_steady",
                 "gallery_c4_g131k.ivf_steady"):
        spec = tiny(cell)
        got = list(calibrate.gallery_controls(spec, [2 ** 31 + 7], n=256))
        ctrl = next(r for r in got if r["who"] == "control")
        assert not _judge(spec, ctrl), (cell, ctrl)
        ref = next(r for r in got if r["who"] == "reference")
        assert _judge(spec, ref), (cell, ref)


def _stale_train(self):
    """A train step that returns its state unchanged."""
    def run(trainable, opt_state, extras, bx, by):
        return trainable, opt_state, bx[:, 0, 0, 0] * 0
    return run


def _half_batches(orig):
    def gather(self, *a, **kw):
        bx, by = orig(self, *a, **kw)
        half = bx.shape[2] // 2
        return bx[:, :, :half], by[:, :, :half]
    return gather


def test_fleet_faults_fail(tiny, drive, monkeypatch):
    from repro.federated.base import Strategy
    spec = tiny("fleet_c100.rounds")
    with monkeypatch.context() as m:
        m.setattr(Strategy, "_stacked_train_fn", _stale_train)
        out, _ = drive(spec)
        assert not out["correct"], out["checks"]
    with monkeypatch.context() as m:
        m.setattr(Strategy, "gather_round_batches",
                  _half_batches(Strategy.gather_round_batches))
        out, _ = drive(spec)
        assert not out["correct"], out["checks"]


def _altered(orig):
    def query(self, qp, qmask, **kw):
        ids, d = orig(self, qp, qmask, **kw)
        ids = ids.copy()
        ids[..., -1] = (ids[..., -1] + ids.shape[-1]) % self.index.capacity
        return ids, d
    return query


def _half_left_out(orig):
    def query(self, qp, qmask, **kw):
        qmask = np.array(qmask)
        qmask[:qmask.shape[0] // 2] = 0.0       # half the cameras' slots
        return orig(self, qp, qmask, **kw)
    return query


@pytest.mark.parametrize("fault", [_altered, _half_left_out])
@pytest.mark.parametrize("cell", ["gallery_c4_g131k.exact_steady",
                                  "gallery_c4_g131k.ivf_steady"])
def test_gallery_faults_fail(cell, fault, tiny, drive, monkeypatch):
    from repro.serving import RetrievalEngine
    monkeypatch.setattr(RetrievalEngine, "query_batch",
                        fault(RetrievalEngine.query_batch))
    out, _ = drive(tiny(cell))
    assert not out["correct"], out["checks"]
