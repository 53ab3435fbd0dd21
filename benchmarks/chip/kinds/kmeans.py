"""Processor kind ``kmeans``: streaming KMeans over messages of points.

* Payloads: a pool of distinct messages, each ``points_per_msg`` float64
  points of ``dim`` coordinates around ``n_clusters`` centres drawn
  uniformly from [-centre_range, centre_range], with normal spread
  ``spread`` (the paper's ``cluster`` source arithmetic), replayed in an
  order drawn from the seed.
* Outputs: the stage's state after each batch is its centroids; the
  collector keeps every batch's, as float32 on the host.
* Plain reference, in ``jax.numpy`` float32 on the chip (the stage casts
  its float64 points to float32): start from the stage's initial centroids
  (standard normal from the seed, stored as float32), and for each recorded
  batch in order assign every point to its nearest centroid (squared
  differences summed over coordinates, ties to the lowest index), then move
  each centroid that got points to ``decay * c + (1 - decay) * mean``. One
  scan over the batches, which gather their points from the pool.
* Control: the same scan in bfloat16.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.traffic import rng_for


class _Msg(NamedTuple):
    value: np.ndarray


def seeded_options(cfg: dict, seed: int) -> dict:
    """The stage draws its initial centroids from ``seed``."""
    return {"seed": abs(int(seed))}


def make_pool(cfg: dict, seed: int) -> list[np.ndarray]:
    sz = cfg["sizes"]
    rng = rng_for(seed, 10)
    r = sz["centre_range"]
    centres = rng.uniform(-r, r, size=(sz["n_clusters"], sz["dim"]))
    pool = []
    for _ in range(sz["pool"]):
        k = rng.integers(0, sz["n_clusters"], size=sz["points_per_msg"])
        pts = centres[k] + rng.normal(0.0, sz["spread"], size=(sz["points_per_msg"], sz["dim"]))
        pool.append(pts.astype(np.float64))
    return pool


def warmup(app, pool: list, cfg: dict) -> None:
    """One batch at each row bucket that 1..max_batch_records messages fill."""
    most = int(cfg["stage"]["max_batch_records"])
    per = cfg["sizes"]["points_per_msg"]
    sizes: dict[int, int] = {}
    for b in range(1, most + 1):
        sizes.setdefault(app.buckets.fit(b * per), b)
    for b in sorted(sizes.values()):
        app.process(None, [_Msg(pool[i % len(pool)]) for i in range(b)])
    app.sync()


class Collector:
    def __init__(self, cfg: dict, seed: int):
        self.kept: dict[int, np.ndarray | None] = {}

    def on_ready(self, batch, result, outputs) -> None:
        self.kept[batch.index] = None if result is None else np.asarray(result, np.float32)

    def finish(self) -> dict:
        return self.kept


def initial_centroids(cfg: dict, seed: int) -> np.ndarray:
    sz = cfg["sizes"]
    c = np.random.default_rng(abs(int(seed))).normal(size=(sz["n_clusters"], sz["dim"]))
    return c.astype(np.float32).astype(np.float64)


@functools.partial(jax.jit, static_argnames=("decay", "dtype"))
def replay(pool, batches, c0, decay: float, dtype: str = "float32"):
    """Centroids after each batch. ``pool`` (P, m, d); ``batches`` (S, B)
    pool indices of each batch's messages in order, -1 where a batch has
    fewer than B; ``c0`` (k, d). Returns (S, k, d) float32."""
    dt = jnp.dtype(dtype)
    k = c0.shape[0]
    pool = pool.astype(dt)

    def one(c, ix):
        pts = pool[jnp.clip(ix, 0)].reshape(-1, pool.shape[-1])
        live = jnp.repeat(ix >= 0, pool.shape[1])
        d2 = jnp.sum(((pts[:, None, :] - c[None]) ** 2).astype(dt), axis=-1, dtype=dt)
        onehot = ((jnp.argmin(d2, axis=1)[:, None] == jnp.arange(k)) & live[:, None]).astype(dt)
        counts = onehot.sum(axis=0, dtype=dt)
        sums = jnp.einsum("nk,nd->kd", onehot, pts, precision="highest",
                          preferred_element_type=dt)
        means = sums / jnp.maximum(counts, 1)[:, None]
        moved = (decay * c + (1 - decay) * means).astype(dt)
        c = jnp.where((counts > 0)[:, None], moved, c)
        return c, c.astype(jnp.float32)

    _, out = jax.lax.scan(one, c0.astype(dt), batches)
    return out


def _replay(record, pool, cfg, seed, dtype: str) -> np.ndarray:
    width = max((len(b.messages) for b in record.batches), default=1)
    idx = np.full((len(record.batches), width), -1, np.int32)
    for r, b in enumerate(record.batches):
        idx[r, :len(b.messages)] = record.order[b.messages]
    decay = float(cfg["stage"]["options"].get("decay", 0.9))
    out = replay(jnp.asarray(np.stack(pool), jnp.float32), jnp.asarray(idx),
                 jnp.asarray(initial_centroids(cfg, seed), jnp.float32), decay, dtype)
    return np.asarray(out, np.float64)


def _readings(record, pool, kept, cfg, seed, low: bool) -> dict:
    want = _replay(record, pool, cfg, seed, "float32")
    got = _replay(record, pool, cfg, seed, "bfloat16") if low else None
    worst = 0.0
    for r, b in enumerate(record.batches):
        g = got[r] if low else kept.get(b.index)
        if g is None or g.shape != want[r].shape or not np.isfinite(g).all():
            worst = math.inf
            continue
        gap = float(np.max(np.abs(np.asarray(g, np.float64) - want[r])) / np.max(np.abs(want[r])))
        worst = max(worst, gap)
    return {"centroid_gap": {"value": worst, "limit": cfg["check"]["centroid_gap"],
                             "pass_if": "<="},
            "batches_compared": {"value": len(record.batches),
                                 "limit": cfg["check"]["min_batches"], "pass_if": ">="}}


def compare(record, pool, kept, cfg, seed) -> dict:
    """Largest gap, over every batch, between the stage's centroids after it
    and the reference's, relative to the largest reference coordinate."""
    return _readings(record, pool, kept, cfg, seed, low=False)


def control(record, pool, kept, cfg, seed) -> dict:
    """The same numbers with the bfloat16 reference in the program's place."""
    return _readings(record, pool, kept, cfg, seed, low=True)


def work(cfg: dict) -> dict:
    """Operations and bytes a message's points need: per point, k squared
    distances of d coordinates (3 ops each) and k compares, then d adds."""
    sz = cfg["sizes"]
    k, d, p = sz["n_clusters"], sz["dim"], sz["points_per_msg"]
    return {"assign_flops": p * k * (3 * d + 1), "update_flops": p * d,
            "assign_bytes": p * (4 * d + 8)}
