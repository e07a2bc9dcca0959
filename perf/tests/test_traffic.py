"""Traffic generators at tiny sizes."""
import time

import numpy as np


def test_poisson_schedule_fixed_work_per_seed():
    from perf.traffic import pacer
    a = pacer.poisson_schedule(2 ** 31 + 11, 500.0, 2.0, 4)
    b = pacer.poisson_schedule(2 ** 31 + 11, 500.0, 2.0, 4)
    c = pacer.poisson_schedule(5, 500.0, 2.0, 4)
    assert len(a.times) == len(c.times) == 1000
    np.testing.assert_array_equal(a.times, b.times)
    assert not np.array_equal(a.times, c.times)
    assert np.all(np.diff(a.times) >= 0) and a.times.max() < 2.0
    assert np.bincount(a.clients).tolist() == [250] * 4
    first, second = a.split(1.0)
    assert len(first.times) + len(second.times) == 1000
    assert second.times.min() >= 0.0 and first.times.max() < 1.0


class _Ticket:
    def __init__(self, t, qid):
        self.t_submit, self.t_launch, self.t_done = t, None, None
        self.qid = qid
        self.ids, self.dists = np.array([qid]), np.zeros((1,), np.float32)


class _Batcher:
    """Answers whatever is queued, one launch of 2 ms per step."""

    def __init__(self):
        self.queue = []

    @property
    def pending(self):
        return len(self.queue)

    def submit(self, client, proto, qid=-1, now=None):
        t = _Ticket(now, qid)
        self.queue.append(t)
        return t

    def step(self):
        launch = time.perf_counter()
        time.sleep(0.002)
        done = time.perf_counter()
        out, self.queue = self.queue, []
        for t in out:
            t.t_launch, t.t_done = launch, done
        return out


def test_open_loop_answers_everything_and_reports_lag():
    from perf.traffic import pacer
    sched = pacer.poisson_schedule(3, 400.0, 0.5, 2)
    run = pacer.run_open_loop(_Batcher(), sched,
                              np.zeros((len(sched.times), 4), np.float32),
                              0.5)
    assert np.isfinite(run.t_done).all()
    assert (run.lag >= 0).all() and run.lag.max() < 0.1
    assert sum(l.slots for l in run.launches) == len(sched.times)
    assert run.answered_by(run.t_done.max()) == len(sched.times)
    # latency counts from the scheduled arrival, never from the submit
    np.testing.assert_array_equal(run.t_submit, run.t0 + sched.times)
    assert (run.latency >= run.lag).all() and (run.queue_s >= 0).all()
    # every answer lands in its arrival's row
    np.testing.assert_array_equal(run.ids[:, 0], np.arange(len(sched.times)))


def test_clustered_gallery_and_queries():
    from perf.traffic import gallery_data
    model = {"img_dim": 256, "proto_dim": 128, "hidden": 128,
             "feat_dim": 64, "n_classes": 512}
    g = {"n_per_id": 8, "id_rank": 16, "id_rho": 0.22}
    heads, rows, centres = gallery_data.make(7, 2, 256, model, g)
    assert rows.shape == (2, 256, 128) and centres.shape == (2, 32, 128)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(rows), axis=-1), 1,
                               rtol=1e-5)
    assert heads["head"]["w"].shape == (2, 64, 512)
    # rows of one identity lie closer to each other than to the next
    r = np.asarray(rows[0])
    same = np.linalg.norm(r[0] - r[1])
    other = np.linalg.norm(r[0] - r[8])
    assert same < other
    q, pick = gallery_data.queries(7, centres, np.array([0, 1, 1]), 0.22)
    assert q.shape == (3, 128) and pick.shape == (3,)
    again, _ = gallery_data.queries(7, centres, np.array([0, 1, 1]), 0.22)
    np.testing.assert_array_equal(q, again)


def test_fleet_data_shapes_and_determinism():
    from perf.traffic.fleet_data import FleetData
    kw = dict(n_clients=3, n_tasks=2, img_dim=256, n_identities=20,
              ids_per_task=4, samples_per_id=10, train_frac=0.6,
              drift_scale=0.15, camera_scale=0.5, move_prob=0.7)
    a, b = FleetData(seed=9, **kw), FleetData(seed=9, **kw)
    t = a.task(2, 1)
    assert t.train_x.shape == (24, 256) and t.query_x.shape == (16, 256)
    np.testing.assert_array_equal(t.train_x, b.task(2, 1).train_x)
    assert a.gallery_members(0, 1) == [(1, 0), (2, 0), (1, 1), (2, 1)]
