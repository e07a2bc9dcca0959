"""Knowledge relevance across the spatial-temporal dimension (paper Eq. 5).

The server keeps the last ``k`` rounds of task features for every client.
Relevance between client i's *current* task and client j is the
forgetting-ratio-decayed sum of similarities against j's task history:

    W_ij^(t) = sum_{t'=t-k..t} lambda_f^{t-t'} * S_ij^(t,t')

Rows are normalised over j != i so Eq. (6) is a convex combination of
neighbour parameters (self-knowledge already lives in A_c / alpha_c).

Two server implementations share this module:

  * ``backend="loop"`` — the original O(C²·k) Python reference, one device
    round-trip per (i, j, age) similarity. Kept as the allclose oracle.
  * batched (default) — histories live in a device-resident ``(C, k, D)``
    ring buffer with a ``(C, k)`` validity mask (``DeviceRingHistory``,
    updated by one batched roll/scatter per round via the tracker's
    ``push_all``; per-client ``push`` falls back to re-stacking the host
    lists) and all-pairs decayed relevance is one ``(C, C·k)`` similarity
    matrix (the Pallas KL kernel for ``metric="kl"``) contracted against
    the decay vector on device. ``backend`` then selects the kernel path
    (``ref`` / ``pallas`` / ``interpret``); ``None`` picks the compiled
    kernel on TPU and the jnp oracle elsewhere.

``decayed_relevance`` is the shared Eq. 4/5 primitive: the device
engine's ``ring_relevance`` calls it per shard inside ``shard_map`` and the
host tracker calls it for all clients at once.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.common import compat
from repro.core.similarity import SIMILARITY_FNS, pairwise_similarity


def decayed_relevance(cur, hist, decay, valid=None, *, metric: str = "kl",
                      backend: Optional[str] = None):
    """Batched Eq. 4/5: decayed all-pairs relevance.

    cur: (N, D) current task features; hist: (C, k, D) per-client task
    histories; decay: (k,) per-slot decay weights (aligned with hist's k
    axis); valid: optional (C, k) {0,1} mask for ragged histories.
    Returns (N, C) *unnormalized* relevance (no diagonal masking).

    ``backend`` selects the KL similarity kernel path only: cosine and
    euclidean have a single jnp implementation (no Pallas kernel) and
    ignore it.
    """
    C, k, D = hist.shape
    flat = hist.reshape(C * k, D)
    if metric == "kl":
        from repro.kernels import ops
        S = ops.kl_similarity(cur, flat, backend=backend)
    else:
        S = pairwise_similarity(cur, flat, metric=metric)
    S = S.reshape(cur.shape[0], C, k)
    if valid is not None:
        S = S * valid[None, :, :]
    return jnp.einsum("nck,k->nc", S, decay.astype(jnp.float32))


def normalize_rows(W: np.ndarray) -> np.ndarray:
    """Row-normalise, leaving all-zero rows (no relevant neighbours) zero."""
    W = np.asarray(W, np.float32)
    rows = W.sum(1, keepdims=True)
    return np.divide(W, rows, out=np.zeros_like(W), where=rows > 0)


@jax.jit
def _ring_push(buf, valid, stale, feats, mask):
    """Batched roll/scatter ring update: age-major shift (most recent at
    age 0) for rows selected by ``mask``; unselected rows are untouched.
    ``stale`` is the per-client rounds-since-last-push counter — pushed
    rows reset to 0, skipped rows age by 1 (the telemetry signal the
    async scheduler's staleness decay will consume)."""
    rolled = jnp.roll(buf, 1, axis=1).at[:, 0].set(feats)
    rvalid = jnp.roll(valid, 1, axis=1).at[:, 0].set(1.0)
    keep = mask > 0
    buf = jnp.where(keep[:, None, None], rolled, buf)
    valid = jnp.where(keep[:, None], rvalid, valid)
    stale = jnp.where(keep, jnp.zeros_like(stale), stale + 1.0)
    return buf, valid, stale


def ring_relevance(buf, valid, *, mesh, forgetting_ratio: float,
                   metric: str = "kl", backend: Optional[str] = None):
    """Unnormalized (C, C) decayed relevance over a ring-buffer history:
    each client's latest feature (age 0) vs every history, rows without a
    current feature zeroed. Diagonal NOT masked — the fused aggregate
    kernel owns that. jit-traceable; the FedSTIL device server program's
    Eq. 4/5 stage.

    The ring rows live on the ``mesh``'s "data" axis, and the rows are
    computed per shard inside ``shard_map``: each shard all-gathers the
    (small) histories and scores its own clients' current features
    against them, so the similarity kernel runs on local blocks. W comes
    back row-sharded."""
    def shard(b, v):
        hist = jax.lax.all_gather(b, "data", tiled=True)
        hvalid = jax.lax.all_gather(v, "data", tiled=True)
        decay = forgetting_ratio ** jnp.arange(hist.shape[1],
                                               dtype=jnp.float32)
        W = decayed_relevance(b[:, 0], hist, decay, hvalid, metric=metric,
                              backend=backend)
        return W * v[:, 0][:, None]

    return compat.shard_map(
        shard, mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=P("data"), check_vma=False)(buf, valid)


@dataclasses.dataclass
class DeviceRingHistory:
    """Device-resident (C, k, D) task-feature history (age-major: most
    recent at age 0) with a (C, k) validity mask.

    The layout is identical to ``RelevanceTracker.stacked_history`` — which
    stays as the host-list oracle — but the buffer lives on device between
    rounds and is updated by one batched roll/scatter per round instead of
    being re-stacked from Python lists.
    """

    n_clients: int
    history_len: int
    dim: int

    def __post_init__(self):
        C, k, D = self.n_clients, self.history_len, self.dim
        self.buf = jnp.zeros((C, k, D), jnp.float32)
        self.valid = jnp.zeros((C, k), jnp.float32)
        # rounds since each client last pushed (telemetry + async-scheduler
        # staleness signal); rides the same ring-push program
        self.stale = jnp.zeros((C,), jnp.float32)

    def push_all(self, feats, mask=None):
        """feats: (C, D) this round's task features; mask: optional (C,)
        {0,1} participation (rows with 0 keep their history untouched)."""
        feats = jnp.asarray(feats, jnp.float32)
        if mask is None:
            mask = jnp.ones((self.n_clients,), jnp.float32)
        self.buf, self.valid, self.stale = _ring_push(
            self.buf, self.valid, self.stale, feats,
            jnp.asarray(mask, jnp.float32))

    def place(self, mesh):
        """Shard the ring's client rows over the mesh's "data" axis (the
        device engine's layout from ``sharding.specs``): the roll/scatter
        push and Eq. 4/5 relevance then run as SPMD programs with each
        device updating only its resident client block. n_clients must
        already be the mesh-padded Cp."""
        from repro.sharding import specs as shard_specs
        sh = jax.sharding.NamedSharding
        self.buf = jax.device_put(
            self.buf, sh(mesh, shard_specs.client_row_spec(3)))
        self.valid = jax.device_put(
            self.valid, sh(mesh, shard_specs.client_row_spec(2)))
        self.stale = jax.device_put(
            self.stale, sh(mesh, shard_specs.client_row_spec(1)))

    def stacked(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        return self.buf, self.valid


@dataclasses.dataclass
class RelevanceTracker:
    n_clients: int
    history_len: int = 6          # k in Eq. (5)
    forgetting_ratio: float = 0.5  # lambda_f
    metric: str = "kl"
    # "loop" = Python reference; otherwise the kernel backend for the
    # batched path (kl only — cosine/euclidean have no kernel and use
    # their single jnp implementation regardless)
    backend: Optional[str] = None

    def __post_init__(self):
        # history[c] = list of task features, most recent last (the oracle
        # layout); the device ring mirrors it once push_all is used
        self.history: List[list] = [[] for _ in range(self.n_clients)]
        self._ring: Optional[DeviceRingHistory] = None
        self._ring_dirty = False   # host lists diverged (per-client push)

    def push(self, client: int, task_feature):
        h = self.history[client]
        h.append(np.asarray(task_feature, np.float32))
        if len(h) > self.history_len:
            h.pop(0)
        self._ring_dirty = True

    def push_all(self, feats, mask=None):
        """Batched push: feats (C, D) for all clients at once, mask an
        optional (C,) participation indicator. Updates the device-resident
        ring with one roll/scatter AND the host lists (the loop oracle), so
        ``relevance()`` no longer re-stacks from host every round."""
        feats = np.asarray(feats, np.float32)
        if mask is None:
            mask = np.ones((self.n_clients,), np.float32)
        mask = np.asarray(mask, np.float32)
        if self._ring is None or self._ring_dirty:
            # (re)build the ring from the oracle lists, then go resident
            self._ring = DeviceRingHistory(self.n_clients, self.history_len,
                                           feats.shape[-1])
            stacked = self.stacked_history()
            if stacked is not None:
                self._ring.buf = jnp.asarray(stacked[0])
                self._ring.valid = jnp.asarray(stacked[1])
            self._ring_dirty = False
        self._ring.push_all(feats, mask)
        for c in range(self.n_clients):
            if mask[c] > 0:
                h = self.history[c]
                h.append(feats[c].copy())
                if len(h) > self.history_len:
                    h.pop(0)

    def stacked_history(self) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """Dense (C, k, D) age-major history (most recent at age 0) plus a
        (C, k) validity mask; None while every history is empty."""
        C, k = self.n_clients, self.history_len
        D = next((h[-1].shape[-1] for h in self.history if h), None)
        if D is None:
            return None
        dense = np.zeros((C, k, D), np.float32)
        valid = np.zeros((C, k), np.float32)
        for j, h in enumerate(self.history):
            for age, feat in enumerate(reversed(h)):
                if age >= k:
                    break
                dense[j, age] = feat
                valid[j, age] = 1.0
        return dense, valid

    def relevance(self, backend: Optional[str] = None) -> np.ndarray:
        """W (C, C): row i = normalized relevance of neighbours j for i."""
        b = backend if backend is not None else self.backend
        if b == "loop":
            return self._relevance_loop()
        return self._relevance_batched(b)

    def _relevance_batched(self, backend: Optional[str]) -> np.ndarray:
        C, k = self.n_clients, self.history_len
        if self._ring is not None and not self._ring_dirty:
            # device-resident path: no host re-stack, one device program
            dense, valid = self._ring.stacked()
        else:
            stacked = self.stacked_history()
            if stacked is None:
                return np.zeros((C, C), np.float32)
            dense, valid = jnp.asarray(stacked[0]), jnp.asarray(stacked[1])
        cur = dense[:, 0]                     # each client's latest feature
        has_cur = valid[:, 0]                 # rows without history stay 0
        decay = self.forgetting_ratio ** np.arange(k, dtype=np.float32)
        W = decayed_relevance(cur, dense, jnp.asarray(decay), valid,
                              metric=self.metric, backend=backend)
        W = W * has_cur[:, None] * (1.0 - jnp.eye(C, dtype=jnp.float32))
        return normalize_rows(np.asarray(W))

    def _relevance_loop(self) -> np.ndarray:
        """Reference O(C²·k) implementation (one device trip per pair)."""
        C = self.n_clients
        fn = SIMILARITY_FNS[self.metric]
        W = np.zeros((C, C), np.float32)
        for i in range(C):
            if not self.history[i]:
                continue
            cur = jnp.asarray(self.history[i][-1])
            for j in range(C):
                if i == j or not self.history[j]:
                    continue
                acc, hj = 0.0, self.history[j]
                for age, feat in enumerate(reversed(hj)):
                    if age >= self.history_len:
                        break
                    s = float(fn(cur, jnp.asarray(feat)))
                    acc += (self.forgetting_ratio ** age) * s
                W[i, j] = acc
        return normalize_rows(W)
