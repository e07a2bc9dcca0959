"""Federated lifelong simulation driver (paper §V experimental protocol).

C edge clients × T sequential tasks × R communication rounds
(R/T rounds per task, 5 local epochs per round — paper trains 60 rounds over
6 tasks). Each round: extract prototypes → local train → upload → server
integration → dispatch → periodic retrieval evaluation (mAP/CMC, Eq. 7) and
forgetting (Eq. 8), plus exact S2C/C2S byte accounting.

Evaluation (``eval_backend="device"``, the default) is itself batched: all
(client, task) query sets live as padded/masked (C, T, Q, D) device arrays,
gallery prototypes are assembled once per (c, t) from the pre-extracted
query prototypes (the extraction layers are frozen, so they never change)
and padded to a common G, and one jitted program per eval round runs every
client's feature head (vmapped over the stacked eval pytree), all distance
matrices (kernels/pairwise_dist), and mAP/CMC + the per-(c, t) forgetting
bookkeeping inputs on device. ``eval_backend="host"`` retains the original
per-(client, task) numpy loop as the allclose oracle (and the fallback for
ragged benchmarks that cannot be stacked).

Two interchangeable engines drive the rounds:

  * ``engine="host"`` (default) — the original per-client Python loop: one
    jit dispatch per client per epoch, per-client state dicts, the server
    round over host lists of pytrees. Works for every strategy and is the
    allclose oracle for the stacked engine.
  * ``engine="stacked"`` — device-resident rounds for strategies that set
    ``supports_stacked`` (FedSTIL, STL): all C client states live as one
    stacked (C, ...) pytree, per-client minibatches are pre-gathered into
    (C, epochs, B, D) arrays (same rng draw order as the host engine, so
    both engines train on identical batches), local training for all C
    clients is a single vmap-over-clients of a scan-over-epochs, and the
    FedSTIL server round runs as one fused device program over a resident
    (C, k, D) relevance ring buffer. Metrics match the host engine to
    float tolerance; per-round wall time scales to C ≫ 100
    (``benchmarks/run.py --bench server`` tracks the ratio).
  * ``engine="sharded"`` — the stacked round, client-sharded over a
    ``Mesh(("data", "model"))`` of every host device: state, batches, the
    relevance ring and all eval inputs are placed row-sharded over "data"
    (``sharding/specs.py`` is the layout source of truth; C is padded to a
    multiple of the device count, padding rows masked out of the relevance
    ring) and the same jitted programs re-specialize into SPMD; the
    programs that call Pallas kernels (relevance, aggregate, codec, eval)
    run them per shard inside ``shard_map``. Wire-bound
    buffers cross shards in bf16 (``common/precision.py``); optimizer/BN
    state stays f32. Metrics and measured comm bytes match the stacked
    engine (``benchmarks/run.py --bench mesh`` scales C → 10k).

Strategies that need raw images (iCaRL) or non-batchable local steps
(EWC/MAS consolidation, FedWeIT sparse uploads) simply keep the default
host engine.

Wire codecs (``Strategy(codec="topk+int8")``, see repro/comm/codec.py)
change what moves on the client<->server path in BOTH engines: every
upload/dispatch is encoded to real wire buffers, the comm log records the
MEASURED buffer bytes next to the analytic formulas
(``SimulationResult.comm_breakdown()``), and the receiver operates on the
decoded — possibly lossy — payload, so compression fidelity shows up in
the metrics. The stacked engine encodes all C clients' payload rows in one
jitted device program (kernels/topk_pack + kernels/quantize).
"""
from __future__ import annotations

import dataclasses
import inspect
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.accounting import CommLog
from repro.core import edge_model as EM
from repro.data.synthetic import FederatedReIDBenchmark
from repro.evalreid import evaluate_retrieval
from repro.federated.base import Strategy
from repro.obs import trace as obs
from repro.train.metrics import LifelongTracker

EVAL_RANKS = (1, 3, 5)


@dataclasses.dataclass
class SimulationResult:
    name: str
    tracker: LifelongTracker
    comm: CommLog
    storage_bytes: int
    rounds: List[Dict[str, float]]      # per-eval-round mean metrics
    eval_on_device: bool = False        # batched device eval (else host)

    def final(self, key="mAP") -> float:
        return self.rounds[-1][key] if self.rounds else 0.0

    def final_metrics(self) -> Dict[str, float]:
        return self.rounds[-1] if self.rounds else {}

    def comm_breakdown(self) -> List[Dict[str, int]]:
        """Per-round measured-vs-formula wire bytes (both directions).
        With codecs active the *_wire columns are measured encoded-buffer
        sizes; without, they equal the analytic *_formula columns."""
        return self.comm.round_breakdown()


def _pre_extract_prototypes(bench: FederatedReIDBenchmark, g_params):
    """Extraction layers are frozen, so every task's train/query prototypes
    are computed up front — as ONE vmapped ``extract_prototypes`` call over
    the stacked (C·T, N, img_dim) array when task shapes are uniform (the
    benchmark default), falling back to per-task calls on ragged shapes."""
    C, T = bench.n_clients, bench.n_tasks
    tasks = [bench.task(c, t) for c in range(C) for t in range(T)]
    shapes = {(task.train_x.shape, task.query_x.shape) for task in tasks}
    protos = {}
    if len(shapes) == 1:
        n_train = tasks[0].train_x.shape[0]
        stacked = np.stack([np.concatenate([task.train_x, task.query_x])
                            for task in tasks])
        out = np.asarray(jax.vmap(
            lambda x: EM.extract_prototypes(g_params, x))(stacked))
        for i, task in enumerate(tasks):
            protos[(task.client, task.round)] = (
                out[i, :n_train], task.train_y,
                out[i, n_train:], task.query_y)
    else:
        for task in tasks:
            protos[(task.client, task.round)] = (
                np.asarray(EM.extract_prototypes(g_params, task.train_x)),
                task.train_y,
                np.asarray(EM.extract_prototypes(g_params, task.query_x)),
                task.query_y,
            )
    return protos


class _EvalCache:
    """Eval-round inputs, built once per simulation and reused every round.

    Galleries never change (the extraction layers are frozen and the
    gallery is the other clients' fixed query splits), so their prototypes
    are assembled per (c, t) from the pre-extracted query prototypes —
    never re-extracted per eval round. On top of that, when task shapes
    are uniform (the benchmark default) the query sets are stacked into
    device-resident padded (C, T, Q, D) arrays and the per-t galleries
    into (C, G_max, D) + validity masks (padded to the t = T-1 gallery
    size, so the jitted device eval program compiles exactly once per
    simulation). Galleries for past tasks are evicted as t advances —
    the task stream is monotone, they are never needed again.
    """

    def __init__(self, bench: FederatedReIDBenchmark, protos,
                 device: bool = True):
        self.bench = bench
        self.protos = protos
        C, T = bench.n_clients, bench.n_tasks
        qshapes = {protos[(c, t)][2].shape for c in range(C) for t in range(T)}
        self.uniform = len(qshapes) == 1
        # device stacks are only built when the device path will run them
        # (uniform shapes AND the caller asked for device eval)
        self.device_ready = device and self.uniform
        self._host_gal: Dict[Tuple[int, int], Tuple] = {}
        self._dev_t: Optional[int] = None
        self._dev_gal = None
        self._mesh = None
        self._padded: Optional[int] = None
        if self.device_ready:
            self.qp = jnp.asarray(np.stack(
                [np.stack([protos[(c, t)][2] for t in range(T)])
                 for c in range(C)]).astype(np.float32))        # (C, T, Q, D)
            self.qids = jnp.asarray(np.stack(
                [np.stack([protos[(c, t)][3] for t in range(T)])
                 for c in range(C)]).astype(np.int32))          # (C, T, Q)
            self.g_max = sum(protos[k][2].shape[0]
                             for k in bench.gallery_members(0, T - 1))
            # static per-query match bound for the counting-based ranking,
            # computed once against the LARGEST (t = T-1) galleries — valid
            # for every earlier t (galleries only shrink)
            from repro.evalreid.batched import max_match_bound
            self.max_matches = max(
                max_match_bound(
                    np.asarray(self.qids[c])[None],
                    np.concatenate([protos[k][3] for k in
                                    bench.gallery_members(c, T - 1)])[None])
                for c in range(C))

    def place(self, mesh, padded: int):
        """engine="sharded": pad every stacked eval input's client dim to
        the mesh-padded Cp (edge-replicating the last client row — padding
        rows are computed but never read back) and pin it to the client-row
        sharding from ``sharding.specs``, so the one jitted eval program
        runs SPMD with each device scoring its own client block."""
        if not self.device_ready:
            return
        self._mesh, self._padded = mesh, padded
        self.qp = self._place_rows(self.qp)
        self.qids = self._place_rows(self.qids)
        self._dev_t = None      # rebuild galleries padded + placed

    def _place_rows(self, arr):
        if self._mesh is None:
            return arr
        from repro.sharding import specs as shard_specs
        pad = self._padded - arr.shape[0]
        if pad:
            arr = jnp.concatenate([arr] + [arr[-1:]] * pad)
        sh = jax.sharding.NamedSharding(
            self._mesh, shard_specs.client_row_spec(arr.ndim))
        return jax.device_put(arr, sh)

    def host_gallery(self, c: int, t: int):
        """(gallery prototypes, gallery ids) for client c at task t —
        computed once per (c, t) from the pre-extracted query prototypes."""
        key = (c, t)
        if key not in self._host_gal:
            if self._host_gal and next(iter(self._host_gal))[1] != t:
                self._host_gal.clear()       # t is monotone: evict old tasks
            members = self.bench.gallery_members(c, t)
            self._host_gal[key] = (
                np.concatenate([self.protos[k][2] for k in members]),
                np.concatenate([self.protos[k][3] for k in members]))
        return self._host_gal[key]

    def device_gallery(self, t: int):
        """Stacked (C, G_max, D) gallery prototypes + (C, G_max) ids and
        validity mask for task t (None when the device stacks were not
        built — ragged benchmark or host-only eval)."""
        if not self.device_ready:
            return None
        if self._dev_t != t:
            C = self.bench.n_clients
            D = self.qp.shape[-1]
            gp = np.zeros((C, self.g_max, D), np.float32)
            gids = np.full((C, self.g_max), -1, np.int32)
            gmask = np.zeros((C, self.g_max), np.float32)
            for c in range(C):
                p, y = self.host_gallery(c, t)
                gp[c, :len(p)] = p
                gids[c, :len(y)] = y
                gmask[c, :len(p)] = 1.0
            self._dev_t = t
            self._dev_gal = tuple(
                self._place_rows(jnp.asarray(a)) for a in (gp, gids, gmask))
        return self._dev_gal

    def task_mask(self, t: int):
        C, T = self.bench.n_clients, self.bench.n_tasks
        m = np.zeros((C, T), np.float32)
        m[:, :t + 1] = 1.0
        return self._place_rows(jnp.asarray(m))


def _round_summary(tracker, rnd):
    per_round = {"round": rnd}
    for key in ("mAP",) + tuple(f"R{k}" for k in EVAL_RANKS):
        per_round[key] = tracker.mean_accuracy(rnd, key)
    per_round["forgetting_mAP"] = tracker.mean_forgetting(rnd, "mAP")
    per_round["forgetting_R1"] = tracker.mean_forgetting(rnd, "R1")
    return per_round


def _eval_round(strategy, get_state, bench, cache, tracker, rnd, t):
    """Host eval block (Eq. 7/8), the allclose oracle: per-client retrieval
    over all trained tasks. ``get_state(c)`` yields a ClientState-like view
    for client c. Gallery prototypes come from the per-(c, t) cache."""
    for c in range(bench.n_clients):
        state = get_state(c)
        gal_p, gal_y = cache.host_gallery(c, t)
        gal_f = strategy.features(state, gal_p)
        for tt in range(t + 1):
            _, _, qx, qy = cache.protos[(c, tt)]
            qf = strategy.features(state, qx)
            m = evaluate_retrieval(qf, qy, gal_f, gal_y, ranks=EVAL_RANKS)
            tracker.record(c, tt, rnd, m)
    return _round_summary(tracker, rnd)


def _eval_round_device(strategy, theta_stacked, cache, tracker, rnd, t):
    """Device eval block: every (client, trained task) mAP/CMC in ONE jitted
    program — vmapped feature heads over the stacked eval pytree, all
    distance matrices through the kernels/pairwise_dist path, metric math
    on device. Only the tiny (C, T, metrics) result is read back to feed
    the lifelong tracker (the Eq. 8 forgetting bookkeeping)."""
    gp, gids, gmask = cache.device_gallery(t)
    out = strategy.eval_round_stacked(
        theta_stacked, cache.qp, cache.qids, cache.task_mask(t),
        gp, gids, gmask, ranks=EVAL_RANKS, max_matches=cache.max_matches)
    out = {k: np.asarray(v) for k, v in out.items()}
    for c in range(cache.bench.n_clients):
        for tt in range(t + 1):
            tracker.record(c, tt, rnd,
                           {k: float(out[k][c, tt]) for k in out})
    return _round_summary(tracker, rnd)


def run_simulation(strategy: Strategy, bench: FederatedReIDBenchmark,
                   *, rounds: int = 12, eval_every: int = 2,
                   seed: int = 0, verbose: bool = False,
                   engine: str = "host",
                   eval_backend: str = "device",
                   trace=None) -> SimulationResult:
    """Drive ``rounds`` federated rounds of ``strategy`` over ``bench``.

    ``trace`` turns on telemetry for this run: a path writes the JSONL
    there (summarize with ``python -m repro.obs.report``); an
    ``obs.Tracer`` records into it without closing (the caller owns the
    sink). ``None`` (default) keeps every obs hook on the null tracer —
    no timestamps, no device syncs, no readbacks.
    """
    if trace is None:
        return _run_simulation(strategy, bench, rounds=rounds,
                               eval_every=eval_every, seed=seed,
                               verbose=verbose, engine=engine,
                               eval_backend=eval_backend)
    owns = not isinstance(trace, obs.Tracer)
    tracer = obs.Tracer(trace) if owns else trace
    tracer.meta(kind_detail="run_simulation", engine=engine, rounds=rounds,
                n_clients=bench.n_clients, strategy=strategy.name)
    try:
        with obs.active(tracer):
            return _run_simulation(strategy, bench, rounds=rounds,
                                   eval_every=eval_every, seed=seed,
                                   verbose=verbose, engine=engine,
                                   eval_backend=eval_backend)
    finally:
        if owns:
            tracer.close()


def _run_simulation(strategy: Strategy, bench: FederatedReIDBenchmark,
                    *, rounds: int, eval_every: int, seed: int,
                    verbose: bool, engine: str,
                    eval_backend: str) -> SimulationResult:
    if engine not in ("host", "stacked", "sharded"):
        raise ValueError(f"unknown engine {engine!r}")
    if eval_backend not in ("device", "host"):
        raise ValueError(f"unknown eval_backend {eval_backend!r}")
    if engine in ("stacked", "sharded") and not strategy.supports_stacked:
        raise ValueError(
            f"strategy {strategy.name!r} does not implement the stacked "
            f"engine API; use engine='host'")

    C, T = bench.n_clients, bench.n_tasks
    rounds_per_task = max(1, rounds // T)
    key = jax.random.PRNGKey(seed)

    # shared pre-trained extraction layers (paper: global pretrained weights)
    g_key, *client_keys = jax.random.split(key, C + 1)
    g_params = EM.init_extraction(g_key, strategy.cfg)

    states = {c: strategy.init_client(client_keys[c]) for c in range(C)}
    tracker = LifelongTracker(C)
    comm = CommLog()
    eval_rounds: List[Dict[str, float]] = []

    protos = _pre_extract_prototypes(bench, g_params)
    cache = _EvalCache(bench, protos, device=eval_backend == "device")
    # ragged benchmarks cannot be stacked — fall back to the host oracle
    eval_dev = cache.device_ready

    if engine in ("stacked", "sharded"):
        stacked = strategy.stack_states(states)
        valid_mask = None
        lead = C      # leading client dim of stacked payloads (Cp on a mesh)
        if engine == "sharded":
            # "computation follows data": build the engine mesh, pad + place
            # the stacked state / eval inputs row-sharded over "data", and
            # every existing jitted round program re-specializes into SPMD.
            # Padding clients train on replicated data; their validity-mask
            # zero keeps them out of the relevance ring (W rows/cols zero,
            # nz False), and byte accounting / eval read back only [:C].
            from repro.sharding import specs as shard_specs
            mesh = shard_specs.engine_mesh()
            stacked, valid_mask = strategy.shard_stacked_state(stacked, mesh)
            lead = strategy.padded_clients
            cache.place(mesh, lead)
        for rnd in range(rounds):
            t = min(rnd // rounds_per_task, T - 1)
            protos_list = [protos[(c, t)][0] for c in range(C)]
            labels_list = [protos[(c, t)][1] for c in range(C)]
            with obs.span("round.gather", cat="phase", round=rnd):
                bx, by = strategy.gather_round_batches(stacked, protos_list,
                                                       labels_list)
                with obs.span("gather.upload", cat="stage", round=rnd,
                              h2d_bytes=obs.device_nbytes(bx, by)) as sp:
                    bx, by = sp.sync(strategy.place_batches(
                        jnp.asarray(bx), jnp.asarray(by)))
            with obs.span("round.local_train", cat="phase", round=rnd) as sp:
                stacked, upload = strategy.local_train_stacked(
                    stacked, bx, by, protos_list, labels_list, rnd)
                sp.sync(stacked.trainable)
            if upload is not None:
                # per-client formula from the ACTUAL leading dim (Cp on a
                # mesh), logged for the C real clients — so measured and
                # formula bytes are engine-invariant at any device count
                formula = strategy.stacked_upload_bytes(upload, lead)
                if strategy.upload_codec is not None:
                    # one batched device encode/decode for all C rows; the
                    # server round consumes the decoded (lossy) upload
                    with obs.span("round.encode", cat="phase", round=rnd):
                        upload, measured = strategy.wire_upload_stacked(
                            upload)
                    comm.log_c2s_many(rnd, formula, C, measured=measured)
                else:
                    comm.log_c2s_many(rnd, formula, C)

            if strategy.uses_server and upload is not None:
                with obs.span("round.server", cat="phase", round=rnd) as sp:
                    dispatch = strategy.server_round_stacked(
                        rnd, upload, valid=valid_mask)
                    if dispatch is not None:
                        sp.sync(dispatch)   # dict shape is strategy-specific
                if dispatch is not None:
                    per_client = strategy.stacked_dispatch_bytes(dispatch,
                                                                 lead)
                    if "nz" in dispatch:
                        with obs.span("server.readback", cat="stage",
                                      round=rnd, d2h_bytes=obs.device_nbytes(
                                          dispatch["nz"])):
                            nz = np.asarray(dispatch["nz"])[:C]
                    else:
                        nz = np.ones((C,), bool)
                    if strategy.dispatch_codec is not None:
                        # the stacked wire model is a BROADCAST stream: the
                        # codec encodes (and the delta refs advance for)
                        # ALL C rows every dispatch round, so all C are
                        # shipped and counted — every client can decode,
                        # including nz=False rows it won't apply. The host
                        # engine instead opens a per-client stream at that
                        # client's first non-empty dispatch; under partial
                        # nz its byte totals are lower by design.
                        with obs.span("round.encode", cat="phase",
                                      round=rnd):
                            dispatch, measured = \
                                strategy.wire_dispatch_stacked(dispatch)
                        # formula oracle keeps the host-engine semantics
                        # (one analytic dispatch per nz client)
                        comm.log_s2c_many(rnd, per_client, C,
                                          measured=measured,
                                          n_formula=int(nz.sum()))
                    else:
                        comm.log_s2c_many(rnd, per_client, int(nz.sum()))
                    with obs.span("round.apply", cat="phase",
                                  round=rnd) as sp:
                        stacked = strategy.apply_dispatch_stacked(stacked,
                                                                  dispatch)
                        sp.sync(stacked.extras)

            if (rnd + 1) % eval_every == 0 or rnd == rounds - 1:
                with obs.span("round.eval", cat="phase", round=rnd):
                    if eval_dev:
                        per_round = _eval_round_device(
                            strategy, strategy.eval_theta_stacked(stacked),
                            cache, tracker, rnd, t)
                    else:
                        per_round = _eval_round(
                            strategy,
                            lambda c: strategy.client_view(stacked, c),
                            bench, cache, tracker, rnd, t)
                eval_rounds.append(per_round)
                if verbose:
                    print(f"  [{strategy.name}/stacked] round {rnd}: "
                          f"mAP={per_round['mAP']:.4f} "
                          f"R1={per_round['R1']:.4f} "
                          f"F={per_round['forgetting_mAP']:.4f}")

        storage = max(strategy.storage_bytes(strategy.client_view(stacked, c))
                      for c in range(C))
        return SimulationResult(strategy.name, tracker, comm, storage,
                                eval_rounds, eval_on_device=eval_dev)

    accepts_raw = "raw_images" in inspect.signature(strategy.local_train).parameters

    for rnd in range(rounds):
        t = min(rnd // rounds_per_task, T - 1)
        # EWC/MAS-style methods consolidate importance at task boundaries
        consolidate = ((rnd + 1) % rounds_per_task == 0) or rnd == rounds - 1
        uploads = {}
        with obs.span("round.local_train", cat="phase", round=rnd):
            for c in range(C):
                px, py, _, _ = protos[(c, t)]
                if accepts_raw:
                    task = bench.task(c, t)
                    states[c], up = strategy.local_train(
                        c, states[c], px, py, rnd,
                        raw_images=task.train_x, g_params=g_params,
                        consolidate=consolidate)
                else:
                    states[c], up = strategy.local_train(
                        c, states[c], px, py, rnd, consolidate=consolidate)
                if up is not None:
                    formula = strategy.upload_bytes(up)
                    if strategy.upload_codec is not None:
                        # the server integrates the DECODED (possibly
                        # lossy) upload — exactly what crossed the wire
                        up, measured = strategy.wire_upload(up, c)
                        comm.log_c2s(rnd, formula, measured=measured)
                    else:
                        comm.log_c2s(rnd, formula)
                    uploads[c] = up

        if strategy.uses_server and uploads:
            with obs.span("round.server", cat="phase", round=rnd):
                dispatches = strategy.server_round(rnd, uploads)
            with obs.span("round.apply", cat="phase", round=rnd):
                for c, d in dispatches.items():
                    if d:
                        formula = strategy.dispatch_bytes(d)
                        if strategy.dispatch_codec is not None:
                            d, measured = strategy.wire_dispatch(d, c)
                            comm.log_s2c(rnd, formula, measured=measured)
                        else:
                            comm.log_s2c(rnd, formula)
                        states[c] = strategy.apply_dispatch(states[c], d)

        if (rnd + 1) % eval_every == 0 or rnd == rounds - 1:
            with obs.span("round.eval", cat="phase", round=rnd):
                if eval_dev:
                    per_round = _eval_round_device(
                        strategy, strategy.stack_eval_thetas(states), cache,
                        tracker, rnd, t)
                else:
                    per_round = _eval_round(strategy, lambda c: states[c],
                                            bench, cache, tracker, rnd, t)
            eval_rounds.append(per_round)
            if verbose:
                print(f"  [{strategy.name}] round {rnd}: "
                      f"mAP={per_round['mAP']:.4f} R1={per_round['R1']:.4f} "
                      f"F={per_round['forgetting_mAP']:.4f}")

    storage = max(strategy.storage_bytes(states[c]) for c in range(C))
    return SimulationResult(strategy.name, tracker, comm, storage, eval_rounds,
                            eval_on_device=eval_dev)
