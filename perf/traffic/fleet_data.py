"""The camera fleet's task stream, made on the host from the seed.

Copied from ``repro.data.synthetic.FederatedReIDBenchmark`` (the program's
own synthetic stand-in for the paper's five ReID datasets): a pool of
identities with base appearance vectors; one camera per client with a
fixed affine transform plus a per-task drift; identities that move to the
next camera between tasks with probability ``move_prob`` (the
spatial-temporal correlation FedSTIL's relevance mines); per task
``ids_per_task`` identities × ``samples_per_id`` views, split
``train_frac`` train / rest query, galleries from the other cameras'
query splits. ``run_simulation`` reads it through the same attributes.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import numpy as np


@dataclasses.dataclass
class Task:
    train_x: np.ndarray
    train_y: np.ndarray
    query_x: np.ndarray
    query_y: np.ndarray
    client: int
    round: int


@dataclasses.dataclass
class FleetData:
    n_clients: int
    n_tasks: int
    img_dim: int
    n_identities: int
    ids_per_task: int
    samples_per_id: int
    train_frac: float
    drift_scale: float
    camera_scale: float
    move_prob: float
    seed: int

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        C, T, D = self.n_clients, self.n_tasks, self.img_dim
        self.identity_base = rng.standard_normal(
            (self.n_identities, D)).astype(np.float32)
        self.cam_rot = np.stack([
            np.eye(D, dtype=np.float32) + self.camera_scale
            * rng.standard_normal((D, D)).astype(np.float32) / np.sqrt(D)
            for _ in range(C)])
        self.cam_bias = (self.camera_scale
                         * rng.standard_normal((C, D)).astype(np.float32))
        drift = rng.standard_normal((C, T, D)).astype(np.float32)
        self.drift = np.cumsum(drift * self.drift_scale, axis=1)
        loc = rng.integers(0, C, size=self.n_identities)
        self.location = np.zeros((T, self.n_identities), np.int64)
        for t in range(T):
            self.location[t] = loc
            move = rng.random(self.n_identities) < self.move_prob
            loc = (loc + move.astype(np.int64)) % C
        self._tasks: Dict[Tuple[int, int], Task] = {}
        for t in range(T):
            for c in range(C):
                self._tasks[(c, t)] = self._make_task(rng, c, t)

    def _render(self, rng, ident, client, t, n):
        views = self.identity_base[ident][None] + 0.3 * rng.standard_normal(
            (n, self.img_dim)).astype(np.float32)
        x = (views @ self.cam_rot[client].T + self.cam_bias[client]
             + self.drift[client, t])
        return x.astype(np.float32)

    def _make_task(self, rng, c, t) -> Task:
        here = np.nonzero(self.location[t] == c)[0]
        if len(here) >= self.ids_per_task:
            ids = rng.choice(here, self.ids_per_task, replace=False)
        else:
            extra = rng.choice(self.n_identities,
                               self.ids_per_task - len(here), replace=False)
            ids = np.concatenate([here, extra])
        x = np.concatenate([self._render(rng, i, c, t, self.samples_per_id)
                            for i in ids])
        y = np.repeat(ids.astype(np.int64), self.samples_per_id)
        perm = rng.permutation(len(x))
        x, y = x[perm], y[perm]
        n_train = int(len(x) * self.train_frac)
        return Task(x[:n_train], y[:n_train], x[n_train:], y[n_train:], c, t)

    def task(self, client: int, t: int) -> Task:
        return self._tasks[(client, t)]

    def gallery_members(self, exclude_client: int, upto_task: int):
        return [(c, t) for (c, t) in self._tasks
                if c != exclude_client and t <= upto_task]

    @property
    def n_classes(self) -> int:
        return self.n_identities
