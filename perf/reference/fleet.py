"""Plain reference of FedSTIL's federated round (paper Algorithm 1), for
the first rounds of a stacked-engine simulation.

Per round, every client c (all in task 0, the window's task):
  1. draws its minibatches: a rehearsal pool of ``batch`` stored
     exemplars (once the memory holds any), then per local epoch ``batch``
     current prototypes and ``batch // 2`` pool rows — from one numpy
     generator seeded with the run's seed, client-major, in that order;
  2. trains (alpha_c, A_c) of theta_c = B_c * alpha_c + A_c (Eq. 2) for
     ``epochs`` Adam steps (weight decay, global-norm clip 1.0) on
     cross-entropy through the head (BN over the batch, bias-free
     classifier) plus ``lam_tie`` * |theta - theta_prev|_1;
  3. keeps per identity the ``per_identity`` prototypes whose features lie
     nearest the identity's mean feature (FIFO eviction at
     ``memory_size``), and uploads theta_c with its task feature, the mean
     prototype (Eq. 3);
the wire codec (delta against the decoder's reconstruction, first payload
a dense int8 keyframe, later ones grouped top-``kg`` of 8 in magnitude
order, int8 per 256-value chunk with scale absmax / 127) carries theta up
and the base down; the server pushes the task features into a
``history_len`` ring, scores every client's current feature against every
history by exp(-KL) of softmax distributions, decays by
``forgetting_ratio`` per round of age (Eq. 4/5), zeroes the diagonal,
normalises rows and forms B = W @ theta (Eq. 6), which each client with a
non-zero row takes as its new base.

Everything runs in ``dtype`` (float32 at ``Precision.HIGHEST``: the
reference; bfloat16: the control).

``run`` replays ``first`` rounds (the program's warm-up: relevance ring
and rehearsal memory full, FIFO eviction running) and then ``rounds``
checked rounds, the window's first. The comparison (``compare``) reads,
leaf by leaf, the gap between the program's norm and the reference's,
against the larger of the reference's norm of that leaf and of the
median leaf:
  * ``grad_gap`` — the first checked round's gradients as Adam
    accumulates them: its first moment after the round less its decayed
    value before (``round_gradient``);
  * ``update_gap`` — the change of alpha, A and the base B over the
    checked rounds;
  * ``bytes_gap`` — (round, direction) pairs, every round replayed,
    whose measured wire bytes differ from the reference codec's buffers
    (exact);
  * ``turn_gap`` — 1 - cosine between the program's change over the
    checked rounds and the reference's, the leaves weighed as above (a
    gap of norms cannot see a wrong direction).
Trainable leaves whose reference gradient is under a thousandth of the
median leaf's (moved by round-off alone, such as the bias BN cancels) are
left out of both gaps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from perf.traffic.gallery_data import init_head

HI = jax.lax.Precision.HIGHEST
INV127 = 1.0 / 127.0
GROUP = 8
NEGLIGIBLE = 1e-3          # of the median leaf's reference gradient
B1 = 0.9                   # Adam's first-moment decay


def n_params(m) -> int:
    p, h, f, n = m["proto_dim"], m["hidden"], m["feat_dim"], m["n_classes"]
    return p * h + h + h * f + f + 2 * f + f * n


def _mm(a, b):
    prec = HI if a.dtype == jnp.float32 else None
    return jnp.matmul(a, b, precision=prec)


def _forward(theta, x):
    h = jax.nn.relu(_mm(x, theta["l1"]["w"]) + theta["l1"]["b"])
    f = _mm(h, theta["l2"]["w"]) + theta["l2"]["b"]
    mu = jnp.mean(f, 0)
    sd = jnp.sqrt(jnp.mean(jnp.square(f - mu), 0)) + 1e-5
    fn = (f - mu) / sd * theta["bn"]["scale"] + theta["bn"]["bias"]
    return fn, _mm(fn, theta["head"]["w"])


def _combine(B, tr):
    return jax.tree.map(lambda b, al, a: b * al + a, B, tr["alpha"], tr["A"])


def _loss(tr, B, prev, x, y, lam):
    theta = _combine(B, tr)
    _, z = _forward(theta, x)
    logp = jax.nn.log_softmax(z, -1)
    ce = -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1))
    tie = sum(jnp.sum(jnp.abs(t - p)) for t, p in
              zip(jax.tree.leaves(theta), jax.tree.leaves(prev)))
    return ce + lam * tie


@functools.partial(jax.jit, static_argnames=("hp",))
def _train(tr, m, v, count, B, prev, bx, by, *, hp):
    """All clients (vmapped), ``epochs`` Adam steps each (scan)."""
    lr, wd, lam, b1, b2, eps = hp

    def client(tr, m, v, count, B, prev, bx, by):
        def step(carry, batch):
            tr, m, v, count = carry
            g = jax.grad(_loss)(tr, B, prev, batch[0], batch[1], lam)
            gn = jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                              for l in jax.tree.leaves(g)))
            scale = jnp.minimum(1.0, 1.0 / (gn + 1e-9))
            g = jax.tree.map(lambda l: (l * scale).astype(l.dtype), g)
            count = count + 1
            c = count.astype(jnp.float32)
            m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
            v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
            bc1, bc2 = 1 - b1 ** c, 1 - b2 ** c

            def upd(p, mm, vv):
                u = (-lr * (mm / bc1) / (jnp.sqrt(vv / bc2) + eps)
                     - lr * wd * p)
                return (p + u).astype(p.dtype)
            tr = jax.tree.map(upd, tr, m, v)
            return (tr, m, v, count), None

        (tr, m, v, count), _ = jax.lax.scan(step, (tr, m, v, count),
                                            (bx, by))
        return tr, m, v, count

    return jax.vmap(client)(tr, m, v, count, B, prev, bx, by)


@jax.jit
def _extract(g, x):
    return jnp.tanh(_mm(jnp.tanh(_mm(x, g["w1"])), g["w2"]))


@jax.jit
def _features(theta, x):
    return jax.vmap(lambda t, xx: _forward(t, xx)[0])(theta, x)


# ---- wire codec -----------------------------------------------------------

def _nc(n, chunk):
    return -(-n // chunk)


def _quant(vals, chunk):
    C, n = vals.shape
    pad = _nc(n, chunk) * chunk - n
    vc = jnp.pad(vals, ((0, 0), (0, pad))).reshape(C, -1, chunk)
    scale = jnp.max(jnp.abs(vc), -1, keepdims=True) * INV127
    scale = jnp.where(scale > 0, scale, 1.0).astype(vals.dtype)
    q = jnp.clip(jnp.round(vc / scale), -127.0, 127.0)
    return (q * scale).reshape(C, -1)[:, :n]


@functools.partial(jax.jit, static_argnames=("kg", "chunk", "keyframe"))
def _codec(x, ref, *, kg, chunk, keyframe):
    """One direction, all clients: (reconstruction the receiver sees)."""
    if keyframe:
        return _quant(x, chunk)
    C, P = x.shape
    r = (x - ref).reshape(C, -1, GROUP)
    a = jnp.abs(r)
    i = jnp.arange(GROUP)
    # magnitude rank within the group, ties to the lower index
    beats = (a[..., None, :] > a[..., :, None]) | (
        (a[..., None, :] == a[..., :, None]) & (i[None, :] < i[:, None]))
    rank = jnp.sum(beats, -1)                                # (C, nb, 8)
    slot = (rank[..., None] == jnp.arange(kg)).astype(r.dtype)  # (.., 8, kg)
    vals = jnp.sum(r[..., None] * slot, -2)                  # rank order
    dq = _quant(vals.reshape(C, -1), chunk).reshape(vals.shape)
    dense = jnp.sum(slot * dq[..., None, :], -1)
    return ref + dense.reshape(C, P)


def _codec_bytes(P, kg, chunk, keyframe) -> int:
    if keyframe:
        return P + 4 * _nc(P, chunk)
    K = -(-P // GROUP) * kg
    bits = (GROUP - 1).bit_length()
    return K + 4 * _nc(K, chunk) + bits * _nc(K, 8)


def _flatten(tree):
    leaves = jax.tree.leaves(tree)
    return jnp.concatenate([l.reshape(l.shape[0], -1) for l in leaves], 1)


def _unflatten(mat, like):
    leaves, treedef = jax.tree.flatten(like)
    out, o = [], 0
    for l in leaves:
        n = int(np.prod(l.shape[1:]))
        out.append(mat[:, o:o + n].reshape(l.shape).astype(l.dtype))
        o += n
    return jax.tree.unflatten(treedef, out)


# ---- server ---------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("ratio",))
def _server(buf, valid, feats, up, *, ratio):
    buf = jnp.roll(buf, 1, axis=1).at[:, 0].set(feats)
    valid = jnp.roll(valid, 1, axis=1).at[:, 0].set(1.0)
    C, k, D = buf.shape
    cur = buf[:, 0].astype(jnp.float32)
    hist = buf.reshape(C * k, D).astype(jnp.float32)
    p = jax.nn.softmax(cur, -1)
    kl = (jnp.sum(p * jax.nn.log_softmax(cur, -1), -1)[:, None]
          - jnp.matmul(p, jax.nn.log_softmax(hist, -1).T, precision=HI))
    S = jnp.exp(-kl).reshape(C, C, k) * valid[None]
    W = jnp.einsum("ick,k->ic", S, ratio ** jnp.arange(k, dtype=jnp.float32))
    W = W * valid[:, 0][:, None] * (1.0 - jnp.eye(C))
    rows = jnp.sum(W, 1, keepdims=True)
    Wn = jnp.where(rows > 0, W / jnp.where(rows > 0, rows, 1.0), 0.0)
    base = _mm(Wn.astype(up.dtype), up)
    return buf, valid, base, jnp.sum(Wn, 1) > 0


# ---- rehearsal memory -----------------------------------------------------

class _Memory:
    def __init__(self, capacity, per_identity):
        self.capacity, self.per_identity = capacity, per_identity
        self.protos = self.labels = self.tasks = None

    def __len__(self):
        return 0 if self.protos is None else len(self.protos)

    def add(self, protos, labels, outputs, task):
        keep = []
        for ident in np.unique(labels):
            idx = np.nonzero(labels == ident)[0]
            d = np.linalg.norm(outputs[idx] - outputs[idx].mean(0), axis=1)
            keep.extend(idx[np.argsort(d)[:self.per_identity]].tolist())
        keep = np.asarray(keep, np.int64)
        new = (protos[keep], labels[keep], np.full(len(keep), task))
        if self.protos is None:
            self.protos, self.labels, self.tasks = new
        else:
            self.protos = np.concatenate([self.protos, new[0]])
            self.labels = np.concatenate([self.labels, new[1]])
            self.tasks = np.concatenate([self.tasks, new[2]])
        while len(self) > self.capacity:
            idx = np.nonzero(self.tasks == self.tasks.min())[0]
            drop = idx[:len(self) - self.capacity]
            mask = np.ones(len(self), bool)
            mask[drop] = False
            self.protos, self.labels, self.tasks = (
                self.protos[mask], self.labels[mask], self.tasks[mask])


def _gather(rng, protos, labels, memories, epochs, batch):
    bx, by = [], []
    for c, mem in enumerate(memories):
        p, l = protos[c], labels[c]
        n = len(p)
        pool = None
        if len(mem):
            idx = rng.choice(len(mem), size=min(batch, len(mem)),
                             replace=False)
            pool = (mem.protos[idx], mem.labels[idx])
        ex, ey = [], []
        for _ in range(epochs):
            idx = rng.choice(n, size=min(batch, n), replace=n < batch)
            px, py = p[idx], l[idx]
            if pool is not None:
                ridx = rng.choice(len(pool[0]), size=batch // 2, replace=True)
                px = np.concatenate([px, pool[0][ridx]])
                py = np.concatenate([py, pool[1][ridx]])
            ex.append(px)
            ey.append(py)
        bx.append(np.stack(ex))
        by.append(np.stack(ey))
    return np.stack(bx), np.stack(by)


# ---- the rounds -----------------------------------------------------------

def round_gradient(m_pre, m_post, epochs, b1=B1):
    """A round's ``epochs`` clipped gradients as Adam's first moment
    accumulates them: (m_post - b1**epochs * m_pre) / (1 - b1)."""
    decay = b1 ** epochs
    return jax.tree.map(lambda a, b: (b - decay * a) / (1 - b1), m_pre,
                        m_post)


def run(cfg, data, seed, *, first, rounds, dtype=jnp.float32,
        half_batch=False):
    """``first`` warm-up rounds, then ``rounds`` checked rounds. Returns
    host arrays: ``init`` (trainable and base before the first checked
    round), ``grad`` (that round's gradients, ``round_gradient``),
    ``trained`` and ``base`` (after the last checked round) and ``bytes``
    {(round, "c2s"|"s2c"): bytes} for every round replayed."""
    m, s = cfg["model"], cfg["strategy"]
    C = cfg["n_clients"]
    keys = jax.random.split(jax.random.PRNGKey(seed), C + 1)
    k1, k2 = jax.random.split(keys[0])
    img, pd = m["img_dim"], m["proto_dim"]
    g = {"w1": jax.random.normal(k1, (img, pd)) * (1.0 / jnp.sqrt(img)),
         "w2": jax.random.normal(k2, (pd, pd)) * (1.0 / jnp.sqrt(pd))}
    theta0 = jax.vmap(lambda k: init_head(k, m))(keys[1:])
    cast = functools.partial(jax.tree.map, lambda a: a.astype(dtype))
    B = cast(theta0)
    prev = B
    tr = {"alpha": jax.tree.map(jnp.ones_like, B),
          "A": jax.tree.map(jnp.zeros_like, B)}
    zeros = jax.tree.map(jnp.zeros_like, tr)
    mom, var, count = zeros, zeros, jnp.zeros((C,), jnp.int32)

    tasks = [data.task(c, 0) for c in range(C)]
    x = np.stack([t.train_x for t in tasks])
    labels = np.stack([t.train_y for t in tasks]).astype(np.int64)
    protos = np.asarray(_extract(cast(g), jnp.asarray(x, dtype)))
    feats = jnp.asarray(np.stack([p.astype(np.float32).mean(0)
                                  for p in protos]), dtype)
    memories = [_Memory(s["memory_size"], s["per_identity"])
                for _ in range(C)]
    rng = np.random.default_rng(seed)
    k = s["history_len"]
    buf = jnp.zeros((C, k, pd), dtype)
    valid = jnp.zeros((C, k), jnp.float32)
    kg = max(1, int(round(s["keep_frac"] * GROUP)))
    P = n_params(m)
    hp = (s["lr"], s["weight_decay"], s["lam_tie"], B1, 0.999, 1e-8)
    refs = {"c2s": None, "s2c": None}
    out = {"bytes": {}}
    for rnd in range(first + rounds):
        bx, by = _gather(rng, protos, labels, memories, s["epochs"],
                         s["batch"])
        if half_batch:
            bx, by = bx[:, :, :bx.shape[2] // 2], by[:, :, :by.shape[2] // 2]
        if rnd == first:
            out["init"] = {"trainable": tr, "B": B}
            m_pre = mom
        tr, mom, var, count = _train(tr, mom, var, count, B, prev,
                                     jnp.asarray(bx, dtype),
                                     jnp.asarray(by, jnp.int32), hp=hp)
        if rnd == first:
            out["grad"] = round_gradient(m_pre, mom, s["epochs"])
        theta = _combine(B, tr)
        prev = theta
        outs = np.asarray(_features(theta, jnp.asarray(protos, dtype)),
                          np.float32)
        for c, mem in enumerate(memories):
            mem.add(protos[c], labels[c], outs[c], rnd)
        up = _flatten(theta)
        key = refs["c2s"] is None
        up = _codec(up, refs["c2s"], kg=kg, chunk=s["chunk"], keyframe=key)
        refs["c2s"] = up
        out["bytes"][(rnd, "c2s")] = (_codec_bytes(P, kg, s["chunk"], key)
                                      + 4 * pd)
        buf, valid, base, nz = _server(buf, valid, feats, up,
                                       ratio=s["forgetting_ratio"])
        key = refs["s2c"] is None
        dn = _codec(base, refs["s2c"], kg=kg, chunk=s["chunk"], keyframe=key)
        refs["s2c"] = dn
        out["bytes"][(rnd, "s2c")] = _codec_bytes(P, kg, s["chunk"], key) + 1
        new = _unflatten(dn, B)
        B = jax.tree.map(
            lambda o, n_: jnp.where(nz.reshape((-1,) + (1,) * (o.ndim - 1)),
                                    n_, o), B, new)
    out["trained"] = tr
    out["base"] = B
    return to_host(out)


def to_host(tree):
    return jax.tree.map(lambda a: np.asarray(a, np.float32)
                        if hasattr(a, "dtype") else a, tree)


# ---- the comparison -------------------------------------------------------

def _paths(tree):
    return {jax.tree_util.keystr(p): np.asarray(l, np.float64)
            for p, l in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _gap(got: dict, want: dict, keep) -> float:
    norms = {k: float(np.linalg.norm(v)) for k, v in want.items()}
    med = float(np.median(list(norms.values())))
    worst = 0.0
    for k in keep:
        g = float(np.linalg.norm(got[k]))
        worst = max(worst, abs(g - norms[k]) / max(norms[k], med, 1e-30))
    return worst


def _turn(got: dict, want: dict, keep) -> float:
    """1 - cosine between the program's and the reference's arrays, all
    leaves as one vector, each leaf over the larger of the reference's
    norm of it and of the median leaf (as ``_gap`` weighs them, so leaves
    that round-off alone moves count for little)."""
    norms = {k: float(np.linalg.norm(v)) for k, v in want.items()}
    med = float(np.median(list(norms.values())))
    a = np.concatenate([got[k].ravel() / max(norms[k], med, 1e-30)
                        for k in keep])
    b = np.concatenate([want[k].ravel() / max(norms[k], med, 1e-30)
                        for k in keep])
    den = float(np.linalg.norm(a) * np.linalg.norm(b))
    return 1.0 - (float(a @ b) / den if den > 0 else 0.0)


def compare(got: dict, want: dict) -> dict:
    gg, wg = _paths(got["grad"]), _paths(want["grad"])
    gnorm = {k: float(np.linalg.norm(v)) for k, v in wg.items()}
    med = float(np.median(list(gnorm.values())))
    moved = [k for k, v in gnorm.items() if v >= NEGLIGIBLE * med]

    def change(d):
        out = {"tr" + k: v for k, v in _paths(d["trained"]).items()}
        init = _paths(d["init"]["trainable"])
        out = {k: v - init[k[2:]] for k, v in out.items()}
        b0 = _paths(d["init"]["B"])
        out.update({"B" + k: v - b0[k] for k, v in _paths(d["base"]).items()})
        return out

    gc, wc = change(got), change(want)
    keep = ["tr" + k for k in moved] + [k for k in wc if k.startswith("B")]
    bytes_gap = sum(1 for key, n in want["bytes"].items()
                    if got["bytes"].get(key) != n)
    gk, wk = {k: gc[k] for k in keep}, {k: wc[k] for k in keep}
    return {"grad_gap": _gap(gg, wg, moved),
            "update_gap": _gap(gk, wk, keep),
            "turn_gap": _turn(gk, wk, keep),
            "bytes_gap": float(bytes_gap)}
