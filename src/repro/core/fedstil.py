"""FedSTIL — the paper's method (Algorithm 1), as a Strategy.

Per round t, per client c:
  1. prototypes P_c^t = G_c(D_c^t) arrive (extraction layers frozen);
  2. server receives only the task feature (mean prototype, Eq. 3);
  3. server computes KL task similarity (Eq. 4), decayed knowledge
     relevance W (Eq. 5), and the personalized base B_c = Σ W_cj θ_j (Eq. 6);
  4. client sets θ_c = B_c ⊙ α_c + A_c (Eq. 2) and trains (α_c, A_c) on a
     mix of current prototypes and rehearsal samples, with parameter tying;
  5. client stores nearest-mean exemplar prototypes; uploads θ_c.

Ablation switches (Table III): ``st_integration``, ``rehearsal``, ``tying``.
Distance metric switch (Table VI): ``metric`` ∈ {kl, cosine, euclidean}.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import compat
from repro.common.precision import to_bf16, to_f32
from repro.common.pytree import (tree_bytes, tree_flatten_stacked,
                                 tree_unflatten_stacked)
from repro.core import edge_model as EM
from repro.core.adaptive import combine, init_adaptive
from repro.core.aggregation import personalized_aggregate
from repro.core.rehearsal import PrototypeMemory, select_exemplars
from repro.core.relevance import (DeviceRingHistory, RelevanceTracker,
                                  normalize_rows)
from repro.core.tying import tying_loss
from repro.federated.base import ClientState, Strategy, pad_client_rows
from repro.kernels import ops
from repro.obs import trace as obs


def sharded_aggregate_fn(mesh, *, backend=None):
    """The engine's Eq. 5→6 aggregate over a mesh, as one jitted
    ``shard_map`` program: ``(W (Cp, Cp), Θ (Cp, P)) -> (B (Cp, P), Wn)``
    with layouts from ``sharding.specs.stacked_aggregate_specs``.

    W, Θ, B and Wn all keep their client rows on "data". Each shard
    all-gathers Θ over "data" (the collective Eq. 6 needs: every base row
    mixes every client) and runs the fused Eq. 5→6 kernel on its own rows
    of W — the rows a row block needs are all local, so every row of B and
    Wn is computed exactly as the one-device kernel computes it. The
    kernel runs per shard because a Pallas call cannot be partitioned by
    the compiler: a jit over sharded operands would not compile on chips.
    """
    key = (mesh, backend)
    if key not in _SHARDED_AGG_CACHE:
        from repro.sharding.specs import stacked_aggregate_specs
        sp = stacked_aggregate_specs()

        def shard(w, theta):
            row0 = jax.lax.axis_index("data") * w.shape[0]
            theta_all = jax.lax.all_gather(theta, "data", tiled=True)
            return ops.fused_relevance_aggregate(w, theta_all, row0,
                                                 backend=backend)

        _SHARDED_AGG_CACHE[key] = jax.jit(compat.shard_map(
            shard, mesh=mesh, in_specs=(sp["w"], sp["thetas"]),
            out_specs=(sp["out"], sp["wn"]), check_vma=False))
    return _SHARDED_AGG_CACHE[key]


_SHARDED_AGG_CACHE: dict = {}

_adaptive_features = jax.jit(EM.adaptive_features)


class FedSTIL(Strategy):
    name = "fedstil"
    uses_server = True
    supports_stacked = True

    def __init__(self, cfg, *, n_clients=5, metric="kl", forgetting_ratio=0.5,
                 history_len=6, memory_size=2000, per_identity=8,
                 lam_tie=1e-4, st_integration=True, rehearsal=True,
                 tying=True, server_backend=None, wire_dtype="bfloat16",
                 **kw):
        super().__init__(cfg, **kw)
        # precision rule (common/precision.py): on a mesh whose "data"
        # axis spans more than one device, the (Cp, P) flatten that
        # crosses the shard boundary is emitted in wire_dtype (bf16
        # default — half the gathered bytes) and upcast to f32 inside the
        # aggregate; "float32" turns the cast off for bit-tight parity
        # runs. On one device nothing crosses and Θ stays f32.
        # Optimizer/BN state is always f32.
        self.wire_dtype = wire_dtype
        self.n_clients = n_clients
        self.lam_tie = lam_tie
        self.st_integration = st_integration
        self.use_rehearsal = rehearsal
        self.use_tying = tying
        self.memory_size = memory_size
        self.per_identity = per_identity
        # server_backend: "loop" reference or a kernel backend for both the
        # batched relevance and the flattened Eq. 6 aggregation
        self.server_backend = server_backend
        self.tracker = RelevanceTracker(
            n_clients, history_len=history_len,
            forgetting_ratio=forgetting_ratio, metric=metric,
            backend=server_backend)
        # device engine: its own device-resident history (the host tracker
        # stays untouched so engine="host" remains the allclose oracle)
        self._ring: Optional[DeviceRingHistory] = None
        self.last_W: Optional[np.ndarray] = None

    # ---- decomposition -------------------------------------------------------
    def init_client(self, key):
        theta0 = EM.init_adaptive_layers(key, self.cfg)
        ad = init_adaptive(theta0)
        st = ClientState(theta=ad.trainable())
        st.extras["reg_B"] = ad.B
        st.extras["reg_prev_theta"] = theta0
        st.extras["memory"] = PrototypeMemory(
            capacity=self.memory_size, per_identity=self.per_identity)
        return st

    def make_theta(self, trainable, extras):
        return combine(extras["reg_B"], trainable["alpha"], trainable["A"])

    def regularizer(self, trainable, extras):
        if not self.use_tying:
            return 0.0
        theta = self.make_theta(trainable, extras)
        return tying_loss(theta, extras["reg_prev_theta"], lam_l1=self.lam_tie)

    def _eval_theta(self, state):
        return self.make_theta(state.theta, state.extras)

    def eval_theta_stacked(self, stacked):
        # theta = B ⊙ alpha + A leaf-wise: the stacked C dim passes through
        return combine(stacked.extras["reg_B"], stacked.trainable["alpha"],
                       stacked.trainable["A"])

    # ---- local round ---------------------------------------------------------
    def local_train(self, client, state, protos, labels, rnd, **_):
        rehearsal = None
        mem: PrototypeMemory = state.extras["memory"]
        if self.use_rehearsal and len(mem):
            rehearsal = mem.sample(self.rng, self.batch)
        state, _ = self._run_epochs(state, protos, labels, rehearsal)

        theta = self._eval_theta(state)
        state.extras["reg_prev_theta"] = theta

        # store exemplar prototypes (nearest-mean, Fig. 4); the features
        # are jitted as in the device engine's ``_exemplar_fn``: fused and
        # op-by-op arithmetic differ in the last bit, enough to reorder two
        # rows of one identity (always tied in exact arithmetic)
        if self.use_rehearsal:
            outputs = _adaptive_features(theta, jnp.asarray(protos))
            mem.add_task(protos, labels, np.asarray(outputs), task_id=rnd)

        # upload: adaptive-layer params + the tiny task feature (Eq. 3)
        task_feature = np.asarray(protos, np.float32).mean(0)
        return state, {"theta": theta, "task_feature": task_feature}

    # ---- server round (spatial-temporal integration) -------------------------
    def server_round(self, rnd, uploads):
        if not self.st_integration or not uploads:
            return {}
        clients = sorted(uploads)
        # one batched roll/scatter into the tracker's device-resident ring
        # (the host lists stay in sync as the loop oracle)
        feats = np.zeros((self.n_clients,
                          np.asarray(uploads[clients[0]]["task_feature"]).shape[-1]),
                         np.float32)
        mask = np.zeros((self.n_clients,), np.float32)
        for c in clients:
            feats[c] = uploads[c]["task_feature"]
            mask[c] = 1.0
        self.tracker.push_all(feats, mask)
        W = self.tracker.relevance()
        self.last_W = W
        # aggregate only rows with relevant neighbours: round 0 (and any
        # client whose neighbours have no history yet) is an all-zero row —
        # skipping it avoids wasted matmul rows and keeps NaNs out entirely.
        # Under partial participation the subset rows are renormalized so
        # Eq. 6 stays a convex combination of the neighbours that DID
        # upload (identity when everyone uploads).
        Wc = normalize_rows(W[np.ix_(clients, clients)])
        nz = np.flatnonzero(Wc.sum(1) > 0)
        out = {c: {} for c in clients}   # {} = no relevant neighbours yet
        if nz.size:
            thetas = [uploads[c]["theta"] for c in clients]
            bases = personalized_aggregate(thetas, Wc[nz],
                                           backend=self.server_backend)
            for row, base in zip(nz, bases):
                out[clients[row]] = {"B": base}
        return out

    def apply_dispatch(self, state, dispatch):
        if "B" in dispatch:
            state.extras["reg_B"] = dispatch["B"]
        return state

    # ---- wire-codec payload split --------------------------------------------
    # Uploads are (theta, task feature): theta is the bulk payload the codec
    # compresses; the Eq. 3 task feature is the server's control plane for
    # relevance (Eq. 4/5) and ships verbatim — letting global top-k compete
    # theta entries against it would distort W for a negligible byte win.
    # Dispatches are (B, engine metadata): only B is wire payload.

    def split_upload_for_wire(self, upload):
        return ({"theta": upload["theta"]},
                {"task_feature": upload["task_feature"]})

    def join_upload_from_wire(self, decoded, verbatim):
        return {"theta": decoded["theta"], **verbatim}

    def split_dispatch_for_wire(self, dispatch):
        verbatim = {k: v for k, v in dispatch.items() if k != "B"}
        return {"B": dispatch["B"]}, (verbatim or None)

    def join_dispatch_from_wire(self, decoded, verbatim):
        return {"B": decoded["B"], **(verbatim or {})}

    def storage_bytes(self, state):
        mem: PrototypeMemory = state.extras["memory"]
        return (tree_bytes(state.theta) + tree_bytes(state.extras["reg_B"])
                + mem.size_bytes)

    # ---- device engine --------------------------------------------------------
    def _gather_rehearsal(self, stacked, c):
        if not self.use_rehearsal:
            return None
        mem: PrototypeMemory = stacked.host["memory"][c]
        if not len(mem):
            return None
        return mem.sample(self.rng, self.batch)

    def _rehearsal_rows(self, stacked):
        if self.use_rehearsal and len(stacked.host["memory"][0]):
            return self.batch // 2
        return 0

    def local_train_stacked(self, stacked, bx, by, protos_list, labels_list,
                            rnd):
        with obs.span("local.train", cat="stage", round=rnd) as sp:
            stacked, _ = super().local_train_stacked(
                stacked, bx, by, protos_list, labels_list, rnd)
            # theta = B ⊙ alpha + A for all clients at once (leaf-wise, so
            # the stacked leading dim passes straight through)
            theta = combine(stacked.extras["reg_B"],
                            stacked.trainable["alpha"], stacked.trainable["A"])
            sp.sync((stacked.trainable, theta))
        stacked.extras["reg_prev_theta"] = theta

        C = len(protos_list)
        if self.use_rehearsal:
            protos = np.stack(protos_list)                    # (C, N, D)
            labels = np.stack(labels_list)                    # (C, N)
            with obs.span("local.forward", cat="stage", round=rnd,
                          h2d_bytes=obs.device_nbytes(protos, labels),
                          d2h_bytes=C * (protos.shape[1] + 1) * 4):
                order, count = jax.device_get(
                    self._exemplar_fn()(theta, protos, labels))
            with obs.span("local.rehearsal", cat="stage", round=rnd,
                          rows=int(count.sum())):
                for c, mem in enumerate(stacked.host["memory"]):
                    mem.add_selected(protos_list[c], labels_list[c],
                                     order[c, :count[c]], task_id=rnd)

        lead = jax.tree.leaves(theta)[0].shape[0]
        D = np.shape(protos_list[0])[-1]
        with obs.span("local.task_feature", cat="stage", round=rnd,
                      h2d_bytes=lead * D * 4) as sp:
            # mesh padding rows: the validity mask keeps them out of the
            # relevance ring, so their values never matter
            feats = pad_client_rows(np.stack(
                [np.asarray(p, np.float32).mean(0) for p in protos_list]),
                lead)
            task_feature = sp.sync(jnp.asarray(feats))
        return stacked, {"theta": theta, "task_feature": task_feature}

    def _exemplar_fn(self):
        """One jit: every client's adaptive-layer features of its task's
        prototypes and their nearest-mean exemplars (``select_exemplars``),
        what ``PrototypeMemory.add_task`` picks client by client on the
        host. ``(theta, protos (C, N, D), labels (C, N)) -> (order (C, N),
        count (C,))``; only the selection comes back to the host."""
        if "exemplar_select" not in self._jit_cache:
            per_identity = self.per_identity

            @jax.jit
            def run(theta, protos, labels):
                # theta carries Cp >= C rows (mesh padding); the host
                # memories exist only for the C real clients
                C = protos.shape[0]
                theta = jax.tree.map(lambda l: l[:C], theta)
                feats = jax.vmap(EM.adaptive_features)(theta, protos)
                return select_exemplars(feats, labels, per_identity)
            self._jit_cache["exemplar_select"] = run
        return self._jit_cache["exemplar_select"]

    def _server_fns(self, theta_example):
        """The server round's staged programs, built once against
        ``self.mesh`` (client rows on "data"): ring push + Eq. 4/5
        relevance, flatten, the fused Eq. 5→6 aggregate, unflatten. Zero
        host round-trips between them.

        Deliberately NOT one mega-jit: on CPU, fusing the (C, P) flatten
        into the aggregate defeats XLA's fast GEMM path (measured ~2.5x
        slower at C=100).

        The relevance scores each shard's rows inside ``shard_map`` (see
        ``ring_relevance``); the aggregate is ``sharded_aggregate_fn``:
        one all-gather of Θ over "data", then the fused kernel per shard
        on its own W rows. Θ crosses a shard boundary only when "data"
        has more than one device; only then does the flatten emit it in
        ``wire_dtype`` (``to_bf16`` halves the gathered bytes), and the
        aggregate upcasts it back with ``to_f32``.
        """
        if "server" not in self._jit_cache:
            backend = (None if self.server_backend == "loop"
                       else self.server_backend)
            ratio = self.tracker.forgetting_ratio
            metric = self.tracker.metric
            mesh = self.mesh
            wire = (self.wire_dtype == "bfloat16"
                    and mesh.shape["data"] > 1)
            agg = sharded_aggregate_fn(mesh, backend=backend)

            # the ring buffer/validity/staleness are the round-carried
            # server state: the caller overwrites all three with the
            # returns, so donate them. ``mask`` is the client-validity
            # mask: mesh-padding rows never enter the ring, so their W
            # rows AND columns stay zero and nz leaves them alone. The
            # telemetry mets are (C,)-sized outputs of this same launch —
            # the host only reads them back when a tracer is active
            # (obs.metric is a no-op otherwise).
            @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
            def relevance(buf, valid, stale, feats, mask):
                from repro.core.relevance import _ring_push, ring_relevance
                from repro.obs import metrics as obsm
                buf, valid, stale = _ring_push(buf, valid, stale, feats,
                                               mask)
                W = ring_relevance(buf, valid, mesh=mesh,
                                   forgetting_ratio=ratio, metric=metric,
                                   backend=backend)
                mets = obsm.relevance_metrics(W, valid, stale)
                return buf, valid, stale, W, mets

            def flatten(th):
                flat = tree_flatten_stacked(th)[0]
                return to_bf16(flat) if wire else flat

            _, meta = tree_flatten_stacked(theta_example)   # one eager call
            self._jit_cache["server"] = (
                relevance, jax.jit(flatten),
                jax.jit(lambda W, flat: agg(W, to_f32(flat))),
                jax.jit(lambda m: tree_unflatten_stacked(m, meta)))
        return self._jit_cache["server"]

    def server_round_stacked(self, rnd, upload, valid):
        """Eq. 4/5 → Eq. 6 as a device-resident program over the ring
        buffer. No host round-trips besides the tiny (Cp, Cp) relevance
        readback for ``last_W``. ``valid`` is the (Cp,) client-validity
        mask: it gates the ring push, so mesh-padding rows never acquire
        history and their relevance rows/columns stay zero."""
        if not self.st_integration:
            return None
        feats = upload["task_feature"]                       # (Cp, D)
        if self._ring is None:
            self._ring = DeviceRingHistory(feats.shape[0],
                                           self.tracker.history_len,
                                           int(feats.shape[-1]))
            self._ring.place(self.mesh)
        relevance, flatten, aggregate, unflatten = self._server_fns(
            upload["theta"])
        with obs.span("server.relevance", cat="stage", round=rnd) as sp:
            (self._ring.buf, self._ring.valid, self._ring.stale, W_raw,
             mets) = relevance(self._ring.buf, self._ring.valid,
                               self._ring.stale, jnp.asarray(feats), valid)
            sp.sync(W_raw)
        with obs.span("server.flatten", cat="stage", round=rnd) as sp:
            flat = sp.sync(flatten(upload["theta"]))          # (Cp, P)
        with obs.span("server.aggregate", cat="stage", round=rnd) as sp:
            B_flat, Wn = sp.sync(aggregate(W_raw, flat))
        # per-client round observables (staleness, ring fill, W row
        # mass/density) — computed inside the relevance launch above;
        # this is a no-op readback unless a tracer is active
        obs.metric("server.relevance", mets, round=rnd)
        with obs.span("server.readback", cat="stage", round=rnd,
                      d2h_bytes=obs.device_nbytes(Wn)):
            self.last_W = np.asarray(Wn)
        # all-zero rows (no relevant neighbours yet) keep their old base
        nz = jnp.sum(Wn, axis=1) > 0
        with obs.span("server.unflatten", cat="stage", round=rnd) as sp:
            B = sp.sync(unflatten(B_flat))
        return {"B": B, "nz": nz}

    def apply_dispatch_stacked(self, stacked, dispatch):
        nz = dispatch["nz"]
        stacked.extras["reg_B"] = jax.tree.map(
            lambda old, new: jnp.where(
                jnp.reshape(nz, (-1,) + (1,) * (old.ndim - 1)),
                new.astype(old.dtype), old),
            stacked.extras["reg_B"], dispatch["B"])
        return stacked

    def stacked_dispatch_bytes(self, dispatch, n_clients: int) -> int:
        return tree_bytes(dispatch["B"]) // max(n_clients, 1)
