"""Shared machinery for federated/lifelong strategies.

A Strategy owns per-client state and defines three hooks:

    local_train(client, state, task_protos, labels, rnd)  -> state, upload
    server_round(rnd, uploads)                            -> dispatches
    apply_dispatch(state, dispatch)                       -> state

The simulation (repro/federated/simulation.py) drives C clients through the
task stream, moving exactly the payloads each strategy declares — the comm
log measures those payloads, reproducing the paper's S2C/C2S accounting.

Strategies that set ``supports_stacked = True`` additionally implement the
*stacked* engine API: all C clients' trainable pytrees, optimizer states,
and loss extras live as ONE pytree whose leaves carry a leading (C, ...)
dim (``StackedClientState``), per-client minibatches are pre-gathered on
host into (C, epochs, B, D) arrays (drawing from ``self.rng`` in exactly
the per-client order the host path uses, so both engines see identical
batches), and local training for all C clients runs as a single
``jax.vmap``-over-clients of a ``lax.scan`` over epochs — one jit dispatch
per round instead of C×epochs.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.registry import register_program
from repro.comm.batched import BatchedCodec
from repro.comm.codec import make_codec
from repro.common import compat
from repro.core import edge_model as EM
from repro.evalreid.batched import batched_retrieval_metrics
from repro.obs import trace as obs
from repro.sharding import specs as shard_specs
from repro.train.optimizer import adam, apply_updates, clip_by_global_norm


@dataclasses.dataclass
class ClientState:
    theta: Any                        # the *trainable* pytree (strategy-defined)
    opt_state: Any = None
    extras: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class StackedClientState:
    """All C clients' states as one device-resident stacked pytree.

    ``trainable`` / ``opt_state`` / ``extras`` leaves carry a leading C
    dim; ``host`` keeps per-client objects that cannot live on device
    (e.g. rehearsal memories) as plain length-C lists.
    """

    n_clients: int
    trainable: Any
    opt_state: Any
    extras: Dict[str, Any] = dataclasses.field(default_factory=dict)
    host: Dict[str, List[Any]] = dataclasses.field(default_factory=dict)


def _is_stackable(value) -> bool:
    """True when every leaf of ``value`` is an array (device-stackable)."""
    return all(isinstance(l, (jnp.ndarray, np.ndarray, jax.Array))
               or np.isscalar(l) for l in jax.tree.leaves(value))


def _stacked_eval_abstract():
    """Bench-scale abstract eval-round inputs (C=8 stacked clients)."""
    S, f32, i32 = jax.ShapeDtypeStruct, jnp.float32, jnp.int32
    cfg = EM.EdgeModelConfig()
    C, T, Q, G, D = 8, 3, 16, 96, cfg.proto_dim
    th = jax.eval_shape(lambda k: EM.init_adaptive_layers(k, cfg),
                        jax.random.PRNGKey(0))
    th = jax.tree.map(lambda l: S((C,) + l.shape, l.dtype), th)
    return ((th, S((C, T, Q, D), f32), S((C, T, Q), i32), S((C, T), f32),
             S((C, G, D), f32), S((C, G), i32), S((C, G), f32)),
            {"ranks": (1, 3, 5), "kernel_backend": "ref", "max_matches": 4})


@register_program(
    "federated.stacked_eval",
    abstract_args=_stacked_eval_abstract,
    oracle="repro.federated.simulation._eval_round", budget_bytes=64 << 20)
def stacked_eval_program(theta, qp, qids, task_mask, gp, gids, gmask, *,
                         ranks=(1, 3, 5), kernel_backend=None,
                         max_matches=None):
    """One traceable retrieval-eval round for all C clients x T tasks.

    theta: stacked eval-time adaptive pytree (leaves (C, ...));
    qp: (C, T, Q, D) query prototypes — ALL tasks' sets, including ones
    not yet trained (their rows hold real data; they are excluded via
    ``task_mask``, which sentinels their query ids to -2 so they can
    never match); qids: (C, T, Q); task_mask: (C, T) 1.0 = trained task;
    gp: (C, G, D) gallery prototypes padded to a common G; gids: (C, G);
    gmask: (C, G) gallery validity.

    The per-client feature heads are vmapped over the stacked pytree —
    gallery features use the masked BN variant (per-client gallery
    statistics over valid rows only), each (c, t) query set gets its own
    BN batch exactly like the per-client host path. Returns the
    ``batched_retrieval_metrics`` dict of (C, T) arrays.
    """
    gal_f = jax.vmap(
        lambda th, p, m: EM.adaptive_forward_masked(th, p, m)[0])(
            theta, gp, gmask)
    qf = jax.vmap(lambda th, sets: jax.vmap(
        lambda p: EM.adaptive_forward(th, p)[0])(sets))(theta, qp)
    qids_eff = jnp.where(task_mask[:, :, None] > 0,
                         qids.astype(jnp.int32), -2)
    return batched_retrieval_metrics(qf, qids_eff, gal_f, gids, gmask=gmask,
                                     ranks=ranks, backend=kernel_backend,
                                     max_matches=max_matches)


# The engine's ONE sharded eval program: the same ``stacked_eval_program``
# body the single-device engine jits, run per shard inside ``shard_map``
# over the mesh's "data" axis — every input leads with the client dim
# (layouts from sharding/specs), retrieval eval never mixes clients, so
# each shard scores its own client block with no collective, and the
# distance kernel sees local blocks (a Pallas call cannot be partitioned
# by the compiler). The tiny (C, T) metric outputs come back row-sharded
# for the host readback. Cached per (mesh, config) — both
# ``Strategy.eval_round_stacked`` under ``engine="sharded"`` and the
# ``launch/eval_round`` CLI call this, so there is exactly one sharded
# eval implementation in the repo.
_SHARDED_EVAL_CACHE: Dict[Any, Callable] = {}


def sharded_eval_fn(mesh, *, ranks=(1, 3, 5), kernel_backend=None,
                    max_matches=None):
    key = (mesh, tuple(ranks), kernel_backend, max_matches)
    if key not in _SHARDED_EVAL_CACHE:
        rows = shard_specs.client_row_spec(1)
        _SHARDED_EVAL_CACHE[key] = jax.jit(compat.shard_map(
            functools.partial(stacked_eval_program, ranks=tuple(ranks),
                              kernel_backend=kernel_backend,
                              max_matches=max_matches),
            mesh=mesh, in_specs=(rows,) * 7, out_specs=rows,
            check_vma=False))
    return _SHARDED_EVAL_CACHE[key]


def pad_client_rows(tree, n_to: int):
    """Pad every leaf's leading client dim to ``n_to`` by edge-replicating
    the last row. Replication (not zeros) keeps padded clients numerically
    boring: their forward/backward passes and eval rows compute real values
    (no 0/0 BN statistics), and the relevance mask guarantees they never
    influence a real client."""
    def pad(l):
        C = l.shape[0]
        if C == n_to:
            return l
        reps = jnp.broadcast_to(l[-1:], (n_to - C,) + l.shape[1:])
        return jnp.concatenate([jnp.asarray(l), reps], axis=0)
    return jax.tree.map(pad, tree)


class Strategy:
    """Base: plain local training (STL)."""

    name = "stl"
    uses_server = False
    # opt-in to the device-resident engine (run_simulation(engine="stacked")):
    # the generic machinery below handles any strategy whose loss/regularizer
    # depend only on array-valued ``reg_*`` extras; strategies with
    # non-batchable local steps (raw-image rehearsal, consolidation hooks,
    # sparse uploads) keep the host engine.
    supports_stacked = False

    def __init__(self, cfg: EM.EdgeModelConfig, *, lr=1e-3, weight_decay=1e-5,
                 epochs=5, batch=64, seed=0, codec=None, codec_opts=None):
        self.cfg = cfg
        self.lr = lr
        self.epochs = epochs
        self.batch = batch
        self.opt = adam(lr=lr, weight_decay=weight_decay)
        self._jit_cache: Dict[str, Callable] = {}
        self.rng = np.random.default_rng(seed)
        # wire codecs (repro.comm.codec): when set, the simulation encodes
        # every upload/dispatch, logs the MEASURED buffer bytes (formulas
        # stay as the cross-check oracle), and the receiver trains on the
        # decoded — possibly lossy — payload. One codec instance per
        # direction so delta state never crosses streams.
        self.codec_spec = codec
        self.codec_opts = dict(codec_opts or {})
        self.upload_codec = make_codec(codec, **self.codec_opts)
        self.dispatch_codec = make_codec(codec, **self.codec_opts)
        self._wire_programs: Dict[Any, BatchedCodec] = {}
        # engine="sharded": set by shard_stacked_state (None = stacked/host)
        self.mesh = None
        self.padded_clients: Optional[int] = None

    # ---- default loss: CE on adaptive layers --------------------------------
    def make_theta(self, trainable, extras):
        """Map the trainable pytree to actual adaptive params (identity for
        most methods; FedSTIL: theta = B ⊙ alpha + A)."""
        return trainable

    def loss(self, trainable, protos, labels, extras):
        return EM.ce_loss(self.make_theta(trainable, extras), protos, labels)

    def regularizer(self, trainable, extras):
        return 0.0

    # ---- generic minibatch trainer ------------------------------------------
    def _train_fn(self):
        if "train" not in self._jit_cache:
            @jax.jit
            def step(trainable, opt_state, protos, labels, extras):
                def lf(th):
                    return (self.loss(th, protos, labels, extras)
                            + self.regularizer(th, extras))
                loss, grads = jax.value_and_grad(lf)(trainable)
                grads, _ = clip_by_global_norm(grads, 1.0,
                                               fixed_order=True)
                updates, opt_state = self.opt.update(grads, opt_state, trainable)
                return apply_updates(trainable, updates), opt_state, loss
            self._jit_cache["train"] = step
        return self._jit_cache["train"]

    def _run_epochs(self, state: ClientState, protos, labels,
                    rehearsal: Optional[Tuple] = None):
        step = self._train_fn()
        n = len(protos)
        opt_state = state.opt_state or self.opt.init(state.theta)
        theta = state.theta
        extras = self._loss_extras(state)
        last = 0.0
        for _ in range(self.epochs):
            idx = self.rng.choice(n, size=min(self.batch, n), replace=n < self.batch)
            px, py = protos[idx], labels[idx]
            if rehearsal is not None:
                rx, ry = rehearsal
                # fixed rehearsal batch (static shapes -> single jit)
                ridx = self.rng.choice(len(rx), size=self.batch // 2, replace=True)
                px = np.concatenate([px, rx[ridx]])
                py = np.concatenate([py, ry[ridx]])
            theta, opt_state, loss = step(theta, opt_state,
                                          jnp.asarray(px), jnp.asarray(py), extras)
            last = float(loss)
        state.theta = theta
        state.opt_state = opt_state
        return state, last

    def _loss_extras(self, state: ClientState):
        ex = {k: v for k, v in state.extras.items() if k.startswith("reg_")}
        return ex if ex else {"reg_dummy": jnp.zeros(())}

    # ---- strategy API --------------------------------------------------------
    def init_client(self, key) -> ClientState:
        return ClientState(theta=EM.init_adaptive_layers(key, self.cfg))

    def local_train(self, client: int, state: ClientState, protos, labels,
                    rnd: int, **_):
        state, loss = self._run_epochs(state, protos, labels)
        return state, None   # STL uploads nothing

    def server_round(self, rnd: int, uploads: Dict[int, Any]) -> Dict[int, Any]:
        return {}

    def apply_dispatch(self, state: ClientState, dispatch) -> ClientState:
        return state

    # comm payload sizing (FedWeIT overrides with sparse accounting)
    def upload_bytes(self, upload) -> int:
        from repro.common.pytree import tree_bytes
        return tree_bytes(upload)

    def dispatch_bytes(self, dispatch) -> int:
        from repro.common.pytree import tree_bytes
        return tree_bytes(dispatch)

    # ---- wire codecs ---------------------------------------------------------
    # What part of a payload goes through the (lossy) codec vs ships
    # verbatim. Default: everything is codec traffic. FedSTIL overrides to
    # keep the tiny Eq. 3 task feature (the server's control plane) exact —
    # top-k sparsification across a concatenated payload would otherwise
    # let large theta entries starve it.

    def split_upload_for_wire(self, upload) -> Tuple[Any, Any]:
        """(codec subtree, verbatim subtree or None) for an upload."""
        return upload, None

    def join_upload_from_wire(self, decoded, verbatim):
        return decoded

    def split_dispatch_for_wire(self, dispatch) -> Tuple[Any, Any]:
        return dispatch, None

    def join_dispatch_from_wire(self, decoded, verbatim):
        return decoded

    def _wire_roundtrip(self, codec, tree, split, join, peer):
        """Encode -> measure -> decode one payload through a host codec
        (single-pass roundtrip: the reconstruction is computed once).
        Returns (the receiver-visible decoded payload, measured bytes
        including verbatim control tensors)."""
        from repro.common.pytree import tree_bytes
        lossy, verbatim = split(tree)
        with obs.span("comm.roundtrip", cat="codec", peer=list(peer)) as sp:
            decoded, payload = codec.roundtrip(lossy, peer=peer)
            sp.sync(decoded)
        measured = payload.nbytes
        if verbatim is not None:
            measured += tree_bytes(verbatim)
        return join(decoded, verbatim), measured

    def wire_upload(self, upload, client: int):
        """Host-engine C2S wire round-trip for one client's upload."""
        return self._wire_roundtrip(
            self.upload_codec, upload, self.split_upload_for_wire,
            self.join_upload_from_wire, ("c2s", client))

    def wire_dispatch(self, dispatch, client: int):
        """Host-engine S2C wire round-trip for one client's dispatch."""
        return self._wire_roundtrip(
            self.dispatch_codec, dispatch, self.split_dispatch_for_wire,
            self.join_dispatch_from_wire, ("s2c", client))

    def _stacked_wire_program(self, which: str, p: int) -> BatchedCodec:
        """Cached device codec program for one direction at payload size p
        (compiled once per simulation — p is fixed by the model)."""
        key = (which, p)
        if key not in self._wire_programs:
            template = (self.upload_codec if which == "upload"
                        else self.dispatch_codec)
            self._wire_programs[key] = BatchedCodec(template, p,
                                                    mesh=self.mesh)
        return self._wire_programs[key]

    def _wire_roundtrip_stacked(self, which, tree, split, join):
        """Stacked-engine wire round-trip: ALL C clients' payload rows are
        encoded/decoded by one jitted device program (Pallas sparsify +
        quantize kernels via kernels.ops); measured per-client bytes come
        from the encoded buffer shapes — zero host readbacks."""
        from repro.common.pytree import (tree_bytes, tree_flatten_stacked,
                                         tree_unflatten_stacked)
        with obs.span("comm.flatten", cat="stage") as sp:
            lossy, verbatim = split(tree)
            mat, meta = tree_flatten_stacked(lossy)
            sp.sync(mat)
        C = mat.shape[0]
        prog = self._stacked_wire_program(which, int(mat.shape[1]))
        with obs.span(f"comm.{which}", cat="codec") as sp:
            recon, buffers = prog.roundtrip(mat)
            sp.sync(recon)
        # rider telemetry from the encode launch (residual norm = decoder-
        # reference staleness, kept energy, keep-rate); no-op readback
        # unless a tracer is active
        obs.metric("comm.encode", prog.last_metrics, direction=which)
        with obs.span("comm.unflatten", cat="stage") as sp:
            per_client = prog.per_client_bytes(buffers)
            if verbatim is not None:
                per_client += tree_bytes(verbatim) // max(C, 1)
            decoded = join(tree_unflatten_stacked(recon, meta), verbatim)
            sp.sync(decoded)
        return decoded, per_client

    def wire_upload_stacked(self, upload):
        return self._wire_roundtrip_stacked(
            "upload", upload, self.split_upload_for_wire,
            self.join_upload_from_wire)

    def wire_dispatch_stacked(self, dispatch):
        return self._wire_roundtrip_stacked(
            "dispatch", dispatch, self.split_dispatch_for_wire,
            self.join_dispatch_from_wire)

    def features(self, state: ClientState, protos):
        feats, _ = EM.adaptive_forward(self._eval_theta(state), jnp.asarray(protos))
        return np.asarray(feats)

    def _eval_theta(self, state: ClientState):
        return state.theta

    # ---- batched (device-resident) evaluation --------------------------------
    def stack_eval_thetas(self, states: Dict[int, "ClientState"]):
        """All C clients' eval-time adaptive params as one (C, ...) pytree
        (host-engine entry to the batched eval program)."""
        from repro.common.pytree import tree_stack
        return tree_stack([self._eval_theta(states[c])
                           for c in range(len(states))])

    def eval_theta_stacked(self, stacked: StackedClientState):
        """Stacked-engine counterpart of ``_eval_theta``: the (C, ...)
        eval-time params, straight off the resident state (no unstack)."""
        return stacked.trainable

    def eval_round_stacked(self, theta, qp, qids, task_mask, gp, gids, gmask,
                           *, ranks=(1, 3, 5), kernel_backend=None,
                           max_matches=None):
        """All C x T retrieval evaluations as one jitted device program
        (feature heads + Pallas distance kernel + mAP/CMC). Under
        ``engine="sharded"`` the same program runs client-row-sharded over
        the engine mesh via ``sharded_eval_fn``."""
        if self.mesh is not None:
            fn = sharded_eval_fn(self.mesh, ranks=tuple(ranks),
                                 kernel_backend=kernel_backend,
                                 max_matches=max_matches)
            return fn(theta, qp, qids, task_mask, gp, gids, gmask)
        key = f"eval:{tuple(ranks)}:{kernel_backend}:{max_matches}"
        if key not in self._jit_cache:
            self._jit_cache[key] = jax.jit(functools.partial(
                stacked_eval_program, ranks=tuple(ranks),
                kernel_backend=kernel_backend, max_matches=max_matches))
        return self._jit_cache[key](theta, qp, qids, task_mask, gp, gids,
                                    gmask)

    def storage_bytes(self, state: ClientState) -> int:
        from repro.common.pytree import tree_bytes
        return tree_bytes(state.theta)

    # ---- stacked (device-resident) engine API --------------------------------
    # One round = gather_round_batches (host rng, same draw order as the
    # host engine) -> local_train_stacked (single jit: vmap over clients of
    # a scan over epochs) -> server_round_stacked / apply_dispatch_stacked
    # (device-resident server program). ``client_view`` materialises one
    # client's slice for evaluation / storage accounting.

    def stack_states(self, states: Dict[int, "ClientState"]) -> StackedClientState:
        """Stack C per-client states into one (C, ...) pytree. Array-valued
        extras are stacked on device; everything else (rehearsal memories,
        host objects) moves to per-client ``host`` lists."""
        from repro.common.pytree import tree_stack
        C = len(states)
        ordered = [states[c] for c in range(C)]
        trainable = tree_stack([s.theta for s in ordered])
        opt_state = jax.vmap(self.opt.init)(trainable)
        extras: Dict[str, Any] = {}
        host: Dict[str, List[Any]] = {}
        for k in ordered[0].extras:
            vals = [s.extras[k] for s in ordered]
            if _is_stackable(vals[0]):
                extras[k] = tree_stack(vals)
            else:
                host[k] = vals
        return StackedClientState(n_clients=C, trainable=trainable,
                                  opt_state=opt_state, extras=extras,
                                  host=host)

    def client_view(self, stacked: StackedClientState, c: int) -> ClientState:
        """Client c's slice of the stacked state (for eval / storage)."""
        from repro.common.pytree import tree_slice
        ex = {k: tree_slice(v, c) for k, v in stacked.extras.items()}
        for k, vals in stacked.host.items():
            ex[k] = vals[c]
        return ClientState(theta=tree_slice(stacked.trainable, c),
                           opt_state=None, extras=ex)

    def _gather_rehearsal(self, stacked: StackedClientState, c: int):
        """Per-client rehearsal pool for this round (None = no rehearsal).
        Called once per client, first in the per-client rng draw order —
        exactly where the host path calls ``memory.sample``."""
        return None

    def _rehearsal_rows(self, stacked: StackedClientState) -> int:
        """Rehearsal rows each epoch batch of this round carries."""
        return 0

    def gather_round_batches(self, stacked: StackedClientState,
                             protos_list, labels_list):
        """Pre-gather every client's epoch minibatches as dense host
        arrays: (C, epochs, B, D) prototypes + (C, epochs, B) labels (the
        round loop uploads them).

        Draws from ``self.rng`` in the host engine's exact order (client-
        major, then epoch; rehearsal pool first, then per-epoch batch and
        rehearsal indices) so both engines train on identical batches.
        """
        C = len(protos_list)
        rows = C * self.epochs * (min(self.batch, len(protos_list[0]))
                                  + self._rehearsal_rows(stacked))
        with obs.span("gather.sample", cat="stage", rows=rows):
            bxs, bys = [], []
            for c in range(C):
                p, l = protos_list[c], labels_list[c]
                n = len(p)
                reh = self._gather_rehearsal(stacked, c)
                ex, ey = [], []
                for _ in range(self.epochs):
                    idx = self.rng.choice(n, size=min(self.batch, n),
                                          replace=n < self.batch)
                    px, py = p[idx], l[idx]
                    if reh is not None:
                        rx, ry = reh
                        ridx = self.rng.choice(len(rx), size=self.batch // 2,
                                               replace=True)
                        px = np.concatenate([px, rx[ridx]])
                        py = np.concatenate([py, ry[ridx]])
                    ex.append(px)
                    ey.append(py)
                bxs.append(np.stack(ex))
                bys.append(np.stack(ey))
            shapes = {b.shape for b in bxs}
            if len(shapes) > 1:
                raise ValueError(
                    f"stacked engine needs uniform per-client batch shapes, "
                    f"got {sorted(shapes)} (ragged tasks/rehearsal pools)")
            return np.stack(bxs), np.stack(bys)

    def _stacked_loss_extras(self, stacked: StackedClientState):
        ex = {k: v for k, v in stacked.extras.items() if k.startswith("reg_")}
        if ex:
            return ex
        # leading dim from the (possibly padded) trainable, not n_clients:
        # the vmapped train program needs every input row count to agree
        lead = jax.tree.leaves(stacked.trainable)[0].shape[0]
        return {"reg_dummy": jnp.zeros((lead,))}

    def _stacked_train_fn(self):
        """One jit: vmap over clients of a lax.scan over pre-gathered epoch
        batches — replaces C×epochs per-client jit dispatches per round."""
        if "stacked_train" not in self._jit_cache:
            # trainable/opt_state are round-carried: the caller overwrites
            # both with the returns, so the old buffers are donated (at
            # C >> 1000 an undonated stacked state doubles peak memory)
            @functools.partial(jax.jit, donate_argnums=(0, 1))
            def run(trainable, opt_state, extras, bx, by):
                def one_client(tr, os, ex, px, py):
                    def estep(carry, batch):
                        tr, os = carry
                        x, y = batch

                        def lf(th):
                            return (self.loss(th, x, y, ex)
                                    + self.regularizer(th, ex))
                        loss, grads = jax.value_and_grad(lf)(tr)
                        grads, _ = clip_by_global_norm(grads, 1.0,
                                               fixed_order=True)
                        updates, os = self.opt.update(grads, os, tr)
                        return (apply_updates(tr, updates), os), loss
                    (tr, os), losses = jax.lax.scan(estep, (tr, os), (px, py))
                    return tr, os, losses[-1]
                return jax.vmap(one_client)(trainable, opt_state, extras,
                                            bx, by)
            self._jit_cache["stacked_train"] = run
        return self._jit_cache["stacked_train"]

    def local_train_stacked(self, stacked: StackedClientState, bx, by,
                            protos_list, labels_list, rnd: int):
        """Train all C clients in one device program. Returns
        (stacked state, stacked upload pytree or None)."""
        run = self._stacked_train_fn()
        extras = self._stacked_loss_extras(stacked)
        trainable, opt_state, _ = run(stacked.trainable, stacked.opt_state,
                                      extras, bx, by)
        stacked.trainable = trainable
        stacked.opt_state = opt_state
        return stacked, None

    def server_round_stacked(self, rnd: int, upload, valid=None):
        """Device-resident server round over the stacked upload. ``valid``
        is the sharded engine's (Cp,) client-validity mask (1.0 for real
        clients, 0.0 for mesh-padding rows); None means every row is real
        (the single-device stacked engine)."""
        return None

    def apply_dispatch_stacked(self, stacked: StackedClientState, dispatch):
        return stacked

    # ---- sharded (mesh-resident) engine API ----------------------------------
    # engine="sharded" reuses the whole stacked round loop; the only deltas
    # are (1) the stacked state/batches are padded to Cp (a multiple of the
    # data-axis size) and placed with client-row NamedShardings so every
    # stacked jit runs SPMD over the mesh, and (2) the server round gets a
    # validity mask so padding rows never enter the relevance ring.

    def shard_stacked_state(self, stacked: StackedClientState, mesh):
        """Pad the stacked state to Cp rows and place it row-sharded on the
        engine mesh. Returns (stacked, valid) where valid is the (Cp,)
        client-validity mask. Host lists (rehearsal memories) stay length
        C — padding rows have no host-side identity."""
        C = stacked.n_clients
        Cp = shard_specs.padded_clients(C, mesh)
        self.mesh = mesh
        self.padded_clients = Cp

        def place(tree):
            padded = pad_client_rows(tree, Cp)
            sh = shard_specs.named_shardings(
                mesh, shard_specs.stacked_tree_specs(padded))
            return jax.device_put(padded, sh)

        stacked.trainable = place(stacked.trainable)
        stacked.opt_state = place(stacked.opt_state)
        stacked.extras = {k: place(v) for k, v in stacked.extras.items()}
        valid = jnp.concatenate([jnp.ones((C,), jnp.float32),
                                 jnp.zeros((Cp - C,), jnp.float32)])
        valid = jax.device_put(valid, jax.sharding.NamedSharding(
            mesh, shard_specs.client_row_spec(1)))
        return stacked, valid

    def place_batches(self, bx, by):
        """Pad this round's (C, epochs, B, ...) minibatch stacks to Cp rows
        and place them row-sharded (no-op outside the sharded engine)."""
        if self.mesh is None:
            return bx, by
        sh = shard_specs.named_shardings(
            self.mesh, shard_specs.stacked_tree_specs((bx, by)))
        return jax.device_put(
            (pad_client_rows(bx, self.padded_clients),
             pad_client_rows(by, self.padded_clients)), sh)

    def stacked_upload_bytes(self, upload, n_clients: int) -> int:
        """Per-client C2S bytes (stacked leaves carry C copies)."""
        from repro.common.pytree import tree_bytes
        return tree_bytes(upload) // max(n_clients, 1)

    def stacked_dispatch_bytes(self, dispatch, n_clients: int) -> int:
        from repro.common.pytree import tree_bytes
        return tree_bytes(dispatch) // max(n_clients, 1)
