"""Prototype rehearsal memory (paper §IV-A, Fig. 4).

Nearest-mean-of-exemplars (iCaRL-style) selection *in prototype space*:
when a task arrives, run its prototypes through the adaptive layers, compute
the per-identity mean of the outputs, and store the prototypes whose outputs
are closest to their identity's mean. Bounded memory, FIFO eviction across
tasks (oldest task's exemplars shrink first), replayed during training.

``PrototypeMemory.add_task`` is the host oracle. The device engine picks
every client's exemplars in one device program (``select_exemplars``) and
stores them through ``add_selected``.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax.numpy as jnp
import numpy as np
from jax import lax


@dataclasses.dataclass
class PrototypeMemory:
    capacity: int                      # max stored prototypes
    per_identity: int = 8              # exemplars per identity per task

    def __post_init__(self):
        self.protos: Optional[np.ndarray] = None   # (N, D)
        self.labels: Optional[np.ndarray] = None   # (N,)
        self.task_ids: Optional[np.ndarray] = None

    def __len__(self):
        return 0 if self.protos is None else len(self.protos)

    @property
    def size_bytes(self) -> int:
        return 0 if self.protos is None else self.protos.nbytes + self.labels.nbytes

    def select(self, labels, outputs) -> np.ndarray:
        """Rows of a new task to keep, in the order they are stored:
        identities ascending (``np.unique``), then each row's distance
        from its identity's mean output ascending, ties by row index; at
        most ``per_identity`` rows per identity.

        labels: (N,) identity ids; outputs: (N, F) adaptive-layer outputs.
        ``select_exemplars`` computes the same order on the device."""
        labels = np.asarray(labels)
        outputs = np.asarray(outputs, np.float32)
        keep_idx: List[int] = []
        for ident in np.unique(labels):
            idx = np.nonzero(labels == ident)[0]
            center = outputs[idx].mean(0)
            d = np.linalg.norm(outputs[idx] - center, axis=1)
            nearest = idx[np.argsort(d, kind="stable")[: self.per_identity]]
            keep_idx.extend(nearest.tolist())
        return np.asarray(keep_idx, np.int64)

    def add_task(self, protos, labels, outputs, task_id: int):
        """Select nearest-mean exemplars of a new task and store them.

        protos: (N, D) prototypes; outputs: (N, F) adaptive-layer outputs
        used for the mean-center distance; labels: (N,) identity ids.
        """
        self.add_selected(protos, labels, self.select(labels, outputs),
                          task_id)

    def add_selected(self, protos, labels, keep_idx, task_id: int):
        """Store rows ``keep_idx`` of a new task's (protos, labels), in
        that order, then evict down to capacity."""
        keep_idx = np.asarray(keep_idx, np.int64)
        new_p = np.asarray(protos)[keep_idx]
        new_l = np.asarray(labels)[keep_idx]
        new_t = np.full((len(keep_idx),), task_id, np.int64)
        if self.protos is None:
            self.protos, self.labels, self.task_ids = new_p, new_l, new_t
        else:
            self.protos = np.concatenate([self.protos, new_p])
            self.labels = np.concatenate([self.labels, new_l])
            self.task_ids = np.concatenate([self.task_ids, new_t])
        self._evict()

    def _evict(self):
        """Shrink oldest tasks first until under capacity."""
        while len(self) > self.capacity:
            oldest = self.task_ids.min()
            idx = np.nonzero(self.task_ids == oldest)[0]
            n_over = len(self) - self.capacity
            drop = idx[: min(n_over, len(idx))]
            mask = np.ones(len(self), bool)
            mask[drop] = False
            self.protos = self.protos[mask]
            self.labels = self.labels[mask]
            self.task_ids = self.task_ids[mask]
            if mask.all():   # safety
                break

    def sample(self, rng: np.random.Generator, n: int):
        """Sample up to n stored prototypes for rehearsal."""
        if self.protos is None or len(self) == 0 or n <= 0:
            return None
        idx = rng.choice(len(self), size=min(n, len(self)), replace=False)
        return self.protos[idx], self.labels[idx]


def select_exemplars(outputs, labels, per_identity: int):
    """``PrototypeMemory.select`` for C clients at once, on the device.

    outputs: (C, N, F) float32 adaptive-layer outputs; labels: (C, N)
    integer identity ids. Returns ``order`` (C, N) int32, each client's row
    indices with its kept rows first, in the order ``select`` gives them
    (the rest follow in the same order), and ``count`` (C,) int32, the
    kept rows per client.

    Memory stays linear in N: rows are sorted by identity, then by
    (identity, distance, row), then kept rows first. The float32
    arithmetic is numpy's, step for step, so the order equals the host's
    wherever the backend rounds as IEEE does (two rows of one identity are
    always tied in exact arithmetic): each identity's mean is a sequential
    sum over its rows in row order, divided by the count, and each
    distance sums its rounded squares in ``numpy_row_sum``'s order. No
    matmul, so nothing rounds to bfloat16 on a TPU.
    """
    C, N, _ = outputs.shape
    row = lax.broadcasted_iota(jnp.int32, (C, N), 1)
    lab, perm = lax.sort((labels.astype(jnp.int32), row), dimension=1,
                         num_keys=2)
    new = lab[:, 1:] != lab[:, :-1]
    edge = jnp.ones((C, 1), bool)
    first = jnp.concatenate([edge, new], axis=1)
    last = jnp.concatenate([new, edge], axis=1)
    start = lax.cummax(jnp.where(first, row, 0), axis=1)
    end = lax.cummin(jnp.where(last, row, N), axis=1, reverse=True)
    size = end - start + 1
    x = jnp.take_along_axis(outputs, perm[..., None], axis=1)

    def add_row(r, total):       # every row sums its whole identity
        xr = jnp.take_along_axis(x, jnp.minimum(start + r, N - 1)[..., None],
                                 axis=1)
        return jnp.where((r < size)[..., None], total + xr, total)

    total = lax.fori_loop(1, jnp.max(size), add_row,
                          jnp.take_along_axis(x, start[..., None], axis=1))
    center = total / size[..., None].astype(jnp.float32)
    # numpy rounds each square before it sums them; fused, a compiler may
    # contract a square and an add into one FMA (XLA's CPU backend does,
    # and drops an optimization barrier). An OR with a zero it cannot
    # prove is zero keeps the squares rounded.
    zero = (jnp.max(size) > N).astype(jnp.uint32)
    sq = lax.bitcast_convert_type(
        lax.bitcast_convert_type(jnp.square(x - center), jnp.uint32) | zero,
        jnp.float32)
    dist = jnp.sqrt(numpy_row_sum(sq))
    # both sorts lead with the identity, so every identity keeps the
    # positions it had, and ``start`` still holds
    _, _, idx = lax.sort((lab, dist, perm), dimension=1, num_keys=3)
    drop = (row - start >= per_identity).astype(jnp.int32)
    _, order = lax.sort((drop, idx), dimension=1, num_keys=1,
                        is_stable=True)
    return order, N - jnp.sum(drop, axis=1)


def numpy_row_sum(v):
    """``np.add.reduce(v, axis=-1)`` of a float32 array, in numpy's own
    order of additions (its pairwise sum: eight running sums in blocks of
    up to 128, halves beyond), so the bits match numpy's."""
    n = v.shape[-1]
    if n < 8:
        res = jnp.zeros(v.shape[:-1], v.dtype)
        for i in range(n):
            res = res + v[..., i]
        return res
    if n <= 128:
        whole = n - n % 8
        r = v[..., :8]
        for i in range(8, whole, 8):
            r = r + v[..., i:i + 8]
        res = (((r[..., 0] + r[..., 1]) + (r[..., 2] + r[..., 3]))
               + ((r[..., 4] + r[..., 5]) + (r[..., 6] + r[..., 7])))
        for i in range(whole, n):
            res = res + v[..., i]
        return res
    half = n // 2
    half -= half % 8
    return numpy_row_sum(v[..., :half]) + numpy_row_sum(v[..., half:])
