"""The one traffic generator: every configuration's events and every mix's
arrivals, made from ``--seed``.

Events (what a stream receives) belong to the configuration
(``config["events"]``):

* ``latent``: dense trackers.  Stream ``i`` owns fixed latent bases
  ``P_i`` (m, L) and ``Q_i`` (n, L) of +-1 entries; its events are
  ``a = P_i x``, ``b = Q_i y`` with ``x``, ``y`` of dyadic entries
  ``k / 2^12``, so ``a`` and ``b`` are exact in float32.  The matrix stays
  ``A0 + P_i C Q_i^T`` with ``C = sum x y^T`` (rank <= seed rank + L <=
  the state rank), so the truncated state is exact and the float64
  reference is exact however long the chain runs.
* ``slots``: a sliding window of measurement rows (network PCA).  Stream
  ``i`` holds an (m, n) window whose rows lie in a fixed rank-``k`` row
  space ``W G_i^T``; an event writes a new row into the oldest slot, which
  is exactly one rank-1 event ``a = e_slot``, ``b = x_new - x_old``.

Arrivals (when, and to which stream) belong to the mix file:

* ``closed``: every stream keeps ``outstanding`` events unseen and submits
  the next as one becomes visible;
* ``open``: ``rate_per_s`` events a second regardless of the system, the
  gaps being the quantiles of the exponential distribution in a seeded
  order, streams chosen by Zipf(``zipf``) counts in a seeded order: every
  seed offers the same work in a different order.

The seed states are made on the device in one jitted call.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

COEF_SCALE = 2.0 ** -12     # dyadic coefficient step: events exact in float32
COEF_MAX = 1024             # coefficients k / 2^12 with |k| <= COEF_MAX
BLOCK = 512                 # events per stream drawn in one block


def _rng(seed: int, *words: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, *words]))


def _jax_key(seed: int):
    w = np.random.SeedSequence([seed, 0]).generate_state(2, np.uint32)
    return jax.random.fold_in(jax.random.key(int(w[0] >> 1)), int(w[1] >> 1))


class _Coefficients:
    """Per-stream sequences of dyadic coefficient rows, drawn in blocks so
    that event ``j`` of stream ``i`` is a function of (seed, i, j) alone."""

    def __init__(self, seed: int, streams: int, width: int, tag: int):
        self.seed, self.width, self.tag = seed, width, tag
        self.first = _rng(seed, tag, 0).integers(
            -COEF_MAX, COEF_MAX + 1, size=(streams, BLOCK, width), dtype=np.int16)
        self.more: dict = {}

    def _block(self, i: int, blk: int) -> np.ndarray:
        if blk == 0:
            return self.first[i]
        key = (i, blk)
        if key not in self.more:
            self.more[key] = _rng(self.seed, self.tag, blk, i).integers(
                -COEF_MAX, COEF_MAX + 1, size=(BLOCK, self.width), dtype=np.int16)
        return self.more[key]

    def take(self, i: int, start: int, count: int) -> np.ndarray:
        """Rows ``start .. start+count-1`` of stream ``i`` as float64."""
        rows, j = [np.zeros((0, self.width), np.int16)], start
        while j < start + count:
            blk, off = divmod(j, BLOCK)
            take = min(BLOCK - off, start + count - j)
            rows.append(self._block(i, blk)[off:off + take])
            j += take
        return np.concatenate(rows).astype(np.float64) * COEF_SCALE


class _Events:
    """What every event model shares: per-stream event counts and the
    float32 seed states, kept on the host for the reference."""

    def _keep(self, u, s, v):
        self.seed_states = tuple(np.asarray(x) for x in jax.device_get((u, s, v)))
        return u, s, v

    def seed_state(self, i: int):
        return tuple(x[i].astype(np.float64) for x in self.seed_states)

    def events_of(self, i: int):
        """Every event stream ``i`` was sent, replayed from the seed:
        ``(a (count, m), b (count, n))`` in float32."""
        replay = type(self).__new__(type(self))
        replay.__dict__.update(self.__dict__)
        replay.count = np.zeros_like(self.count)
        if hasattr(self, "w0"):
            replay.rows = self.w0.copy()
        return replay.next(i, int(self.count[i]))


class LatentEvents(_Events):
    """Dense trackers: rank-1 events inside fixed latent subspaces."""

    def __init__(self, config: dict, seed: int):
        ev = config["events"]
        self.streams, self.m, self.n = config["streams"], config["m"], config["n"]
        self.r, self.L, self.seed_rank = config["rank"], ev["latent_rank"], ev["seed_rank"]
        self.seed = seed
        self.x = _Coefficients(seed, self.streams, self.L, 2)
        self.y = _Coefficients(seed, self.streams, self.L, 3)
        self.count = np.zeros(self.streams, np.int64)

    def device_init(self):
        """Seed states (u, s, v) on the device; keeps the latent bases on
        the host for the generator."""
        u, s, v, p, q = _latent_init(_jax_key(self.seed), self.streams, self.m,
                                     self.n, self.r, self.seed_rank, self.L)
        self.p = np.asarray(jax.device_get(p))
        self.q = np.asarray(jax.device_get(q))
        return self._keep(u, s, v)

    def next(self, i: int, count: int):
        j = int(self.count[i])
        self.count[i] += count
        x = self.x.take(i, j, count).astype(np.float32)
        y = self.y.take(i, j, count).astype(np.float32)
        return x @ self.p[i].T, y @ self.q[i].T

    def reference(self, i: int, u0, s0, v0):
        """float64 factors ``(L, R)`` with ``L R^T`` the stream's matrix
        after every event it was sent."""
        cnt = int(self.count[i])
        c = self.x.take(i, 0, cnt).T @ self.y.take(i, 0, cnt)
        p = self.p[i].astype(np.float64)
        q = self.q[i].astype(np.float64)
        return (np.concatenate([u0 * s0, p], 1), np.concatenate([v0, q @ c.T], 1))


class DriftEvents(LatentEvents):
    """Dense trackers of a slowly drifting full-rank subspace: the seed
    state spans the latent bases (latent rank = state rank) with a flat
    spectrum ``seed_scale`` times an event's norm, so the tracked spectrum
    stays well conditioned however long the chain runs."""

    def __init__(self, config: dict, seed: int):
        ev = dict(config["events"], latent_rank=config["rank"], seed_rank=config["rank"])
        super().__init__(dict(config, events=ev), seed)
        self.seed_scale = float(config["events"]["seed_scale"])

    def device_init(self):
        u, s, v, p, q = _drift_init(_jax_key(self.seed), self.streams, self.m,
                                    self.n, self.r, self.seed_scale)
        self.p = np.asarray(jax.device_get(p))
        self.q = np.asarray(jax.device_get(q))
        return self._keep(u, s, v)


class SlotEvents(_Events):
    """Sliding windows: a new row replaces the oldest, one rank-1 event."""

    def __init__(self, config: dict, seed: int):
        ev = config["events"]
        self.streams, self.m, self.n = config["streams"], config["m"], config["n"]
        self.r, self.k = config["rank"], ev["row_rank"]
        self.seed = seed
        self.w = _Coefficients(seed, self.streams, self.k, 4)
        self.count = np.zeros(self.streams, np.int64)

    def device_init(self):
        u, s, v, g, w0 = _slots_init(_jax_key(self.seed), self.streams, self.m,
                                     self.n, self.r, self.k)
        self.g = np.asarray(jax.device_get(g)).astype(np.float32)
        self.w0 = np.asarray(jax.device_get(w0)).astype(np.float32)   # (S, m, k)
        self.rows = self.w0.copy()
        return self._keep(u, s, v)

    def next(self, i: int, count: int):
        j = int(self.count[i])
        self.count[i] += count
        new = self.w.take(i, j, count).astype(np.float32)
        a = np.zeros((count, self.m), np.float32)
        b = np.empty((count, self.n), np.float32)
        for t in range(count):
            slot = (j + t) % self.m
            a[t, slot] = 1.0
            b[t] = (new[t] - self.rows[i, slot]) @ self.g[i].T
            self.rows[i, slot] = new[t]
        return a, b

    def reference(self, i: int, u0, s0, v0):
        cnt = int(self.count[i])
        rows = self.w0[i].astype(np.float64)
        new = self.w.take(i, 0, cnt)
        for j in range(cnt):
            rows[j % self.m] = new[j]
        delta = (rows - self.w0[i]) @ self.g[i].astype(np.float64).T
        return (np.concatenate([u0 * s0, delta], 1),
                np.concatenate([v0, np.eye(self.n)], 1))


EVENT_MODELS = {"latent": LatentEvents, "drift": DriftEvents, "slots": SlotEvents}


def event_model(config: dict, seed: int):
    return EVENT_MODELS[config["events"]["model"]](config, seed)


def _event_norm2(m, n, latent):
    """Typical ||a|| ||b|| of a latent event: each coordinate of ``a`` has
    variance ``latent`` E[x^2] with x uniform on +-COEF_MAX * COEF_SCALE."""
    var = latent * (COEF_MAX * COEF_SCALE) ** 2 / 3
    return (m * var * n * var) ** 0.5


@partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def _latent_init(key, streams, m, n, r, seed_rank, latent):
    f32 = jnp.float32
    k = jax.random.split(key, 5)
    with jax.default_matmul_precision("highest"):
        u = jnp.linalg.qr(jax.random.normal(k[0], (streams, m, r), f32))[0]
        v = jnp.linalg.qr(jax.random.normal(k[1], (streams, n, r), f32))[0]
    top = jnp.sort(jax.random.uniform(k[2], (streams, seed_rank), f32, 0.5, 1.0),
                   axis=1)[:, ::-1] * (8.0 * m * latent * (COEF_MAX * COEF_SCALE) ** 2 / 3)
    s = jnp.concatenate([top, jnp.zeros((streams, r - seed_rank), f32)], 1)
    p = jax.random.rademacher(k[3], (streams, m, latent), f32)
    q = jax.random.rademacher(k[4], (streams, n, latent), f32)
    return u, s, v, p, q


@partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _drift_init(key, streams, m, n, r, seed_scale):
    f32 = jnp.float32
    k = jax.random.split(key, 3)
    p = jax.random.rademacher(k[0], (streams, m, r), f32)
    q = jax.random.rademacher(k[1], (streams, n, r), f32)
    with jax.default_matmul_precision("highest"):
        u = jnp.linalg.qr(p)[0]
        v = jnp.linalg.qr(q)[0]
    s = jnp.sort(jax.random.uniform(k[2], (streams, r), f32, 1.0, 2.0), axis=1
                 )[:, ::-1] * (seed_scale * _event_norm2(m, n, r))
    return u, s, v, p, q


@partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _slots_init(key, streams, m, n, r, k):
    f32 = jnp.float32
    kg, kw = jax.random.split(key)
    g = jax.random.rademacher(kg, (streams, n, k), f32)
    w0 = jax.random.randint(kw, (streams, m, k), -COEF_MAX, COEF_MAX + 1
                            ).astype(f32) * COEF_SCALE
    with jax.default_matmul_precision("highest"):
        win = w0 @ jnp.swapaxes(g, 1, 2)                 # (S, m, n)
        qm, rm = jnp.linalg.qr(win)                      # (S, m, n), (S, n, n)
        x, sv, yt = jnp.linalg.svd(rm)
        u = qm @ x[:, :, :r]
    keep = jnp.arange(r) < k
    s = jnp.where(keep, sv[:, :r], 0.0)
    v = jnp.swapaxes(yt, 1, 2)[:, :, :r]
    return u, s, v, g, w0


# ---------------------------------------------------------------------------
# arrivals
# ---------------------------------------------------------------------------


def zipf_counts(streams: int, events: int, theta: float) -> np.ndarray:
    """Events per popularity rank (rank 0 hottest) under Zipf(``theta``),
    apportioned by largest remainder so they sum to ``events``."""
    p = 1.0 / np.arange(1, streams + 1) ** theta
    p /= p.sum()
    exact = p * events
    counts = np.floor(exact).astype(np.int64)
    short = events - int(counts.sum())
    counts[np.argsort(counts - exact, kind="stable")[:short]] += 1
    return counts


def open_schedule(mix: dict, streams: int, seconds: float, seed: int):
    """Due times (s from the window's start) and target streams of every
    event of an open-loop window."""
    rate = float(mix["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    _rng(seed, 5).shuffle(gaps)
    due = np.cumsum(gaps)
    ranks = np.repeat(np.arange(streams), zipf_counts(streams, n, mix["zipf"]))
    _rng(seed, 6).shuffle(ranks)
    stream_of_rank = _rng(seed, 7).permutation(streams)
    return due, stream_of_rank[ranks]


def warm_rounds(mix: dict, config: dict):
    """``(depth, width)`` of every round the warm-up seals: the mix lists
    depth and width ranges, ``"max_depth"`` and ``"streams"`` standing for
    the configuration's values."""
    subst = {"max_depth": config["service"]["max_depth"], "streams": config["streams"]}
    out = []
    for entry in mix["warm"]:
        depth = subst.get(entry["depth"], entry["depth"])
        lo, hi = (subst.get(w, w) for w in entry["widths"])
        out.extend((int(depth), w) for w in range(int(lo), int(hi) + 1))
    return out


def sample_streams(counts: np.ndarray, k: int, seed: int) -> list[int]:
    """``k`` streams to check: the one with the longest chain, and the rest
    drawn from the seed."""
    hottest = int(np.argmax(counts))
    rest = [int(i) for i in _rng(seed, 8).permutation(len(counts)) if i != hottest]
    return [hottest] + rest[:max(0, k - 1)]


def harmonic_share(streams: int, theta: float) -> float:
    """The hottest stream's share of events, 1 / H(streams, theta)."""
    return 1.0 / float(sum(1.0 / k ** theta for k in range(1, streams + 1)))

