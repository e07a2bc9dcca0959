"""Federated rounds: ``run_simulation(FedSTIL(...), engine="stacked")``.

One ``run_simulation`` call holds set-up and window, so every jitted
program the strategy caches compiles once. Set-up is data generation,
prototype extraction, client initialisation and ``warmup_rounds`` rounds:
enough for the relevance ring (``history_len`` rounds) and the rehearsal
memory (``memory_size`` / kept exemplars per round) to be full, so the
window runs steady-state rounds. The plain reference replays the warm-up
and follows the window's first ``CHECK_ROUNDS`` rounds.

The benchmark reads the program without changing its path:
  * a tracer of its own, installed through ``obs.active``, takes round
    boundaries from the ``round.gather`` span and ends the window by
    raising ``WindowClosed`` there, after whole rounds; while it is not
    active it neither syncs nor reads anything back;
  * thin wrappers on the strategy instance keep copies of what the
    window's first rounds produced (trained parameters, Adam's first
    moment, the dispatched base) and the measured wire bytes every round
    reports.
Ending the loop by an exception discards ``SimulationResult``; the wire
bytes the window's rounds logged in its ``CommLog`` are the ones the
wrappers recorded as they were returned, and the outputs that decide
``correct`` were copied out as the checked rounds produced them.

With ``--trace 1`` the window's first half runs under the profiler with
``jax.profiler.TraceAnnotation`` spans (no syncs), its second half under
the program's own syncing tracer, which gives the per-phase times.
"""
from __future__ import annotations

import gc
import time

from perf import harness
from perf.reference import fleet as ref
from perf.traffic.fleet_data import FleetData

CHECK_ROUNDS = 3


class _Copies:
    """Wrappers on one strategy instance that record what its rounds
    returned: the measured wire bytes of every round, and copies of the
    state around the checked rounds (``first`` .. ``first +
    CHECK_ROUNDS - 1``, the window's first). Copies are device-side and
    asynchronous; round 0 makes the same copies once, so the window
    compiles nothing for them."""

    def __init__(self, strat, first):
        import jax
        import jax.numpy as jnp
        self._copy = lambda tree: jax.tree.map(jnp.copy, tree)
        self.first, self.last_checked = first, first + CHECK_ROUNDS - 1
        self.round = -1
        self.bytes = {}                       # (round, "c2s"|"s2c") -> B
        self.last = None                      # newest applied base
        self.init = self.m_pre = self.m_post = None
        self.trained = self.base = None
        self.orig = {}
        for name in ("local_train_stacked", "wire_upload_stacked",
                     "wire_dispatch_stacked", "apply_dispatch_stacked"):
            self.orig[name] = getattr(strat, name)
            setattr(strat, name, getattr(self, name))

    def local_train_stacked(self, stacked, bx, by, protos, labels, rnd):
        self.round = rnd
        if rnd in (0, self.first):
            self.init = {"trainable": self._copy(stacked.trainable),
                         "B": self._copy(stacked.extras["reg_B"])}
            self.m_pre = self._copy(stacked.opt_state["m"])
        out = self.orig["local_train_stacked"](stacked, bx, by, protos,
                                               labels, rnd)
        if rnd in (0, self.first):
            self.m_post = self._copy(out[0].opt_state["m"])
        if rnd in (0, self.last_checked):
            self.trained = self._copy(out[0].trainable)
        return out

    def wire_upload_stacked(self, upload):
        dec, measured = self.orig["wire_upload_stacked"](upload)
        self.bytes[(self.round, "c2s")] = int(measured)
        return dec, measured

    def wire_dispatch_stacked(self, dispatch):
        dec, measured = self.orig["wire_dispatch_stacked"](dispatch)
        self.bytes[(self.round, "s2c")] = int(measured)
        return dec, measured

    def apply_dispatch_stacked(self, stacked, dispatch):
        out = self.orig["apply_dispatch_stacked"](stacked, dispatch)
        self.last = out.extras["reg_B"]
        if self.round in (0, self.last_checked):
            self.base = self._copy(self.last)
        return out

    def drain(self):
        import jax
        if self.last is not None:
            jax.block_until_ready(self.last)

    def outputs(self, epochs):
        """What the checked rounds produced, on the host, in the
        reference's form."""
        grad = ref.round_gradient(self.m_pre, self.m_post, epochs)
        return ref.to_host({
            "init": self.init, "grad": grad, "trained": self.trained,
            "base": self.base,
            "bytes": {k: v for k, v in self.bytes.items()
                      if k[0] <= self.last_checked}})


def _clock_class():
    from repro.obs import trace as obs

    class RoundClock(obs.Tracer):
        """Round boundaries from ``round.gather``. Inactive (no syncs, no
        readbacks, the null span) until the traced run's second part,
        which records the program's own spans with their syncs."""

        active = False

        def __init__(self, copies, warmup, seconds, trace, clock):
            super().__init__()
            self.copies, self.warmup = copies, warmup
            self.seconds, self.trace, self.clock = seconds, trace, clock
            self.profile = harness.Profile() if trace else None
            self.part = "setup"
            self.starts = []                  # window part's round starts
            self.t0 = self.t_end = None
            self.n_rounds = self.profiled_rounds = 0
            self.compiles0 = 0

        def _begin(self, part, rnd):
            self.copies.drain()
            self.part, self.first = part, rnd
            self.t0 = time.perf_counter()
            self.starts = []

        def _boundary(self, rnd):
            if rnd == self.warmup:
                gc.collect()
                gc.freeze()     # set-up's objects: out of the window's GC
                self.compiles0 = self.clock.n
                self.setup_end = time.perf_counter()
                if self.trace:
                    self.copies.drain()
                    self.profile.start()
                    self._begin("profile", rnd)
                else:
                    self._begin("window", rnd)
                return
            if self.part == "setup":
                return
            budget = self.seconds / 2 if self.trace else self.seconds
            if time.perf_counter() - self.t0 < budget:
                self.starts.append(rnd)
                return
            self.copies.drain()
            if self.part == "profile":
                self.profile.stop()
                self.profiled_rounds = len(self.starts) + 1
                self.active = True
                self._begin("synced", rnd)
                return
            self.t_end = time.perf_counter()
            self.n_rounds = len(self.starts) + 1
            raise harness.WindowClosed

        def span(self, name, **attrs):
            if name == "round.gather":
                self._boundary(attrs["round"])
            if self.part == "synced":
                return super().span(name, **attrs)
            if self.part == "profile":
                return harness.AnnotatedSpan(name)
            return obs.NullTracer.span(self, name)

        def metric(self, name, values=None, **attrs):
            if self.part == "synced":
                super().metric(name, values, **attrs)

    return RoundClock


def make_strategy(cfg, seed):
    from repro.core import FedSTIL
    from repro.core.edge_model import EdgeModelConfig
    s = cfg["strategy"]
    return FedSTIL(EdgeModelConfig(**cfg["model"]),
                   n_clients=cfg["n_clients"], codec=s["codec"],
                   metric=s["metric"], forgetting_ratio=s["forgetting_ratio"],
                   history_len=s["history_len"], memory_size=s["memory_size"],
                   per_identity=s["per_identity"], lam_tie=s["lam_tie"],
                   lr=s["lr"], weight_decay=s["weight_decay"],
                   epochs=s["epochs"], batch=s["batch"], seed=seed,
                   codec_opts={"keep_frac": s["keep_frac"],
                               "chunk": s["chunk"]})


def make_data(cfg, mix, seed):
    return FleetData(n_clients=cfg["n_clients"], n_tasks=cfg["n_tasks"],
                     img_dim=cfg["model"]["img_dim"], seed=seed,
                     **mix["data"])


def run(spec, *, seed, seconds, trace, clock, t_start, devices):
    import repro.core  # noqa: F401  (before repro.federated: import order)
    from repro.federated import run_simulation
    from repro.obs import trace as obs

    cfg, mix = spec.config, spec.traffic
    data = make_data(cfg, mix, seed)
    strat = make_strategy(cfg, seed)
    first = mix["warmup_rounds"]
    copies = _Copies(strat, first)
    rc = _clock_class()(copies, first, seconds, trace, clock)
    eval_every = mix["eval_every"] or 10 ** 9
    try:
        with obs.active(rc):
            run_simulation(strat, data, engine="stacked",
                           rounds=mix["max_rounds"], eval_every=eval_every,
                           seed=seed)
        raise RuntimeError(f"the window did not close within "
                           f"{mix['max_rounds']} rounds")
    except harness.WindowClosed:
        pass
    window_s = rc.t_end - rc.t0
    n = rc.n_rounds
    wire = [copies.bytes[(r, d)] for r in range(rc.first, rc.first + n)
            for d in ("c2s", "s2c")]
    e2e = {"setup_s": (rc.setup_end - t_start, "s"),
           "round_s": (window_s / n, "s")}
    layer = {"window_compiles": clock.n - rc.compiles0,
             "fleet": True, "rounds": n,
             "wire_bytes_per_round": sum(wire) / n,
             "spans": [e for e in rc.events if e.get("kind") == "span"]}
    if trace:
        layer["profile"] = rc.profile.reduce(harness.kernel_patterns())
        layer["profiled_rounds"] = rc.profiled_rounds
        layer["kernel_shapes"] = kernel_shapes(cfg, mix)
        rc.profile.close()
    peak = harness.memory_peak(devices)

    if copies.round < copies.last_checked:
        raise RuntimeError(f"the window ended before round "
                           f"{copies.last_checked}, the last one checked")
    got = copies.outputs(cfg["strategy"]["epochs"])
    del strat, copies, rc
    gc.collect()
    want = ref.run(cfg, data, seed, first=first, rounds=CHECK_ROUNDS)
    readings = ref.compare(got, want)
    return harness.Record(e2e=e2e, readings=readings, attempted=n, failed=0,
                          memory_peak_bytes=peak, layer=layer)


def kernel_shapes(cfg, mix):
    """Shapes of one steady-state round's device work."""
    m, s, d = cfg["model"], cfg["strategy"], mix["data"]
    P = ref.n_params(m)
    kg = max(1, int(round(s["keep_frac"] * ref.GROUP)))
    per_task = d["ids_per_task"] * d["samples_per_id"]
    n_train = int(per_task * d["train_frac"])
    return {"C": cfg["n_clients"], "P": P, "K": -(-P // ref.GROUP) * kg,
            "chunk": s["chunk"], "epochs": s["epochs"],
            "rows": s["batch"] + s["batch"] // 2, "n_train": n_train,
            "model": m}
