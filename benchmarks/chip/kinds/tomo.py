"""Processor kind ``tomo``: parallel-beam reconstruction of sinogram frames
(the ``gridrec`` and ``mlem`` stages).

* Payloads: a pool of distinct (n_angles, n_det) float32 sinograms, each the
  exact parallel-beam projection of a phantom of random ellipses drawn from
  the seed (the Radon transform of an ellipse is closed-form), replayed in
  an order drawn from the seed.
* Outputs: a stage's state after a batch is the reconstruction of the
  batch's last frame, and the rest of a stack's reconstructions go no
  further. So ``tap`` records every array the stage's two programs (single
  frame and padded stack) return, and the comparison checks every real row
  of every stack, not only the state. The collector keeps a sample of
  batches drawn from the seed, plus the first batch of every stack size
  the run saw.
* Plain reference: the ramp filter in float64 (numpy FFT) and a pixel-driven
  backprojection by linear interpolation (one gather a pixel and angle of
  the two bins it lies between, for a stack of frames at once), and its
  adjoint for mlem, in ``jax.numpy`` with no kernel, on the chip. Geometry: pixel
  centres at integer offsets from the image centre, a pixel at (x, y) lands
  at detector coordinate x cos(t) + y sin(t) + (n_det - 1) / 2, bins outside
  the detector are dropped, and gridrec scales by pi / (2 n_angles).
* Control: the same reference with its data path (sinogram values,
  weights, products and the accumulator) in bfloat16; coordinates stay
  float32.
* Work per frame: each pixel-angle interpolation counts 9 operations (4 for
  the detector coordinate, 1 for the fraction, 3 for the interpolation, 1
  for the accumulation), whatever the implementation evaluates; a complex
  FFT of length N counts 5 N log2 N.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.traffic import rng_for

OPS_PER_INTERP = 9


class _Msg(NamedTuple):
    value: np.ndarray


# -- payloads -------------------------------------------------------------------


def seeded_options(cfg: dict, seed: int) -> dict:
    """Stage options drawn from the seed: none (the stage holds no seeded state)."""
    return {}


def ellipse_sinogram(ellipses: np.ndarray, n_angles: int, n_det: int) -> np.ndarray:
    """Exact parallel-beam projection of a sum of ellipses.

    ``ellipses`` rows are (x0, y0, a, b, phi, density) in pixels from the
    image centre. A line at detector coordinate t from the centre, angle
    theta, crosses an ellipse centred at the origin with half-axes a, b
    rotated by phi over 2 a b sqrt(r^2 - t^2) / r^2, r^2 = (a cos(theta -
    phi))^2 + (b sin(theta - phi))^2."""
    theta = np.arange(n_angles) * (np.pi / n_angles)
    t = np.arange(n_det) - (n_det - 1) / 2.0
    out = np.zeros((n_angles, n_det))
    for x0, y0, a, b, phi, rho in ellipses:
        r2 = (a * np.cos(theta - phi)) ** 2 + (b * np.sin(theta - phi)) ** 2
        tt = t[None, :] - (x0 * np.cos(theta) + y0 * np.sin(theta))[:, None]
        inside = np.maximum(r2[:, None] - tt * tt, 0.0)
        out += 2.0 * rho * a * b * np.sqrt(inside) / r2[:, None]
    return out.astype(np.float32)


def make_pool(cfg: dict, seed: int) -> list[np.ndarray]:
    sz = cfg["sizes"]
    rng = rng_for(seed, 10)
    half = cfg["stage"]["options"]["n"] / 2.0
    pool = []
    for _ in range(sz["pool"]):
        k = sz["ellipses"]
        r = half * 0.6 * np.sqrt(rng.uniform(0, 1, k))
        ang = rng.uniform(0, 2 * np.pi, k)
        ell = np.stack([r * np.cos(ang), r * np.sin(ang),
                        half * rng.uniform(0.05, 0.35, k), half * rng.uniform(0.05, 0.35, k),
                        rng.uniform(0, np.pi, k), rng.uniform(0.1, 1.0, k)], axis=1)
        pool.append(ellipse_sinogram(ell, sz["n_angles"], sz["n_det"]))
    return pool


def warmup(app, pool: list, cfg: dict) -> None:
    """Run one batch of every stack shape the window can make: the
    single-frame program and each stack bucket up to max_batch_records."""
    most = int(cfg["stage"]["max_batch_records"])
    sizes = {1: 1}
    for b in range(2, most + 1):
        sizes.setdefault(app.batch_buckets.fit(b), b)
    for b in sorted(sizes.values()):
        app.process(None, [_Msg(pool[i % len(pool)]) for i in range(b)])
    app.sync()


class _Tapped:
    """A program of the stage that records what it returns."""

    def __init__(self, inner, outputs: list):
        self.inner = inner
        self.outputs = outputs

    def __call__(self, *args, **kwargs):
        out = self.inner(*args, **kwargs)
        self.outputs.append(out)
        return out

    def __getattr__(self, name):  # the jitted program's own attributes
        return getattr(self.inner, name)


def tap(app):
    """Record every reconstruction the stage's programs return. Returns
    ``take()``: the arrays returned since the last call, in call order
    (called on the engine thread, right after each ``process``)."""
    outputs: list = []
    app._rec = _Tapped(app._rec, outputs)
    app._rec_batch = _Tapped(app._rec_batch, outputs)

    def take() -> list:
        got = outputs[:]
        outputs.clear()
        return got
    return take


class Collector:
    """Keeps, as device arrays until the drain, the state and the program
    outputs of a sample of batches: ``check.sample`` of them by reservoir
    sampling from the seed, and the first batch of each stack size."""

    def __init__(self, cfg: dict, seed: int):
        self.k = int(cfg["check"]["sample"])
        self.rng = rng_for(seed, 3)
        self.reservoir: list = []
        self.by_size: dict[int, tuple] = {}
        self.seen = 0

    def on_ready(self, batch, result, outputs) -> None:
        item = (batch, result, outputs)
        self.by_size.setdefault(len(batch.messages), item)
        if self.seen < self.k:
            self.reservoir.append(item)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.k:
                self.reservoir[j] = item
        self.seen += 1

    def finish(self) -> list:
        """[(batch, state, outputs)] by batch, as float32 numpy (None where
        the stage returned none)."""
        items = {b.index: (b, r, o) for b, r, o in self.reservoir}
        items.update({b.index: (b, r, o) for b, r, o in self.by_size.values()})
        self.reservoir, self.by_size = [], {}
        return [(b, _host(r), [_host(x) for x in (o or [])])
                for _, (b, r, o) in sorted(items.items())]


def _host(x):
    return None if x is None else np.asarray(x, np.float32)


# -- plain reference ----------------------------------------------------------------


def ramp_filter_ref(sino: np.ndarray) -> np.ndarray:
    """|frequency| filter along the detector axis, in float64."""
    freqs = np.abs(np.fft.fftfreq(sino.shape[-1]))
    spec = np.fft.fft(np.asarray(sino, np.float64), axis=-1)
    return np.real(np.fft.ifft(spec * freqs, axis=-1))


def _geometry(n: int, n_det: int, cos_t, sin_t):

    c = (n - 1) / 2.0
    x = jnp.arange(n, dtype=jnp.float32) - c
    s = x[None, :] * cos_t + x[:, None] * sin_t + (n_det - 1) / 2.0
    s0 = jnp.floor(s)
    return s0.astype(jnp.int32), s - s0


@functools.partial(jax.jit, static_argnames=("n", "dtype"))
def backproject_ref(sinos, angles, n: int, dtype="float32"):
    """Unfiltered backprojection of a stack, (B, A, n_det) -> (B, n, n), one
    angle at a time. Each pixel reads the pair of bins it lies between with
    one gather from a table whose entry j holds bins (j - 1, j) of every
    frame, zero outside the detector."""
    dt = jnp.dtype(dtype)
    b, _, n_det = sinos.shape
    z = jnp.zeros(sinos.shape[:2] + (1,), sinos.dtype)
    padded = jnp.concatenate([z, sinos, z], axis=-1)
    pairs = jnp.concatenate([padded[..., :-1], padded[..., 1:]], axis=0)  # (2B, A, D+1)
    table = jnp.transpose(pairs, (1, 2, 0)).astype(dt)  # (A, D+1, 2B)

    def one(acc, inp):
        theta, tab = inp
        i0, f = _geometry(n, n_det, jnp.cos(theta), jnp.sin(theta))
        j = i0 + 1
        got = jnp.where(((j >= 0) & (j <= n_det))[..., None], tab[jnp.clip(j, 0, n_det)], 0)
        f = f.astype(dt)[..., None]
        return acc + ((1 - f) * got[..., :b] + f * got[..., b:]).astype(dt), None

    acc, _ = jax.lax.scan(one, jnp.zeros((n, n, b), dt), (angles.astype(jnp.float32), table))
    return jnp.moveaxis(acc, -1, 0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("n_det", "dtype"))
def project_ref(img, angles, n_det: int, dtype="float32"):
    """The adjoint of ``backproject_ref``: (n, n) -> (A, n_det)."""

    dt = jnp.dtype(dtype)
    n = img.shape[0]
    img = img.astype(dt).ravel()

    def one(theta):
        i0, f = _geometry(n, n_det, jnp.cos(theta), jnp.sin(theta))
        i0, f = i0.ravel(), f.ravel().astype(dt)
        # bins outside the detector land in two dump bins and are dropped
        idx0 = jnp.where((i0 >= 0) & (i0 < n_det), i0, n_det)
        idx1 = jnp.where((i0 + 1 >= 0) & (i0 + 1 < n_det), i0 + 1, n_det + 1)
        row = jnp.zeros((n_det + 2,), dt)
        row = row.at[idx0].add((1 - f) * img).at[idx1].add(f * img)
        return row[:n_det]

    return jax.lax.map(one, angles.astype(jnp.float32)).astype(jnp.float32)


def _angles(n_angles: int):

    return jnp.asarray(np.arange(n_angles) * (np.pi / n_angles), jnp.float32)


def gridrec_ref(sinos: np.ndarray, n: int, dtype: str = "float32") -> np.ndarray:
    """(B, A, n_det) -> (B, n, n), float64 out."""
    a = sinos.shape[1]
    filtered = jnp.asarray(ramp_filter_ref(sinos), jnp.float32)
    bp = backproject_ref(filtered, _angles(a), n=n, dtype=dtype)
    return np.asarray(bp, np.float64) * (np.pi / (2.0 * a))


def mlem_ref(sino: np.ndarray, n: int, iters: int, dtype: str = "float32") -> np.ndarray:

    a, n_det = sino.shape
    angles = _angles(a)
    y = jnp.asarray(sino, jnp.float32)
    eps = 1e-6
    norm = backproject_ref(jnp.ones_like(y)[None], angles, n=n, dtype=dtype)[0] + eps
    x = jnp.ones((n, n), jnp.float32)
    for _ in range(iters):
        fp = project_ref(x, angles, n_det=n_det, dtype=dtype)
        ratio = y / jnp.maximum(fp, eps)
        x = x * backproject_ref(ratio[None], angles, n=n, dtype=dtype)[0] / norm
    return np.asarray(x, np.float64)


#: frames the gridrec reference backprojects in one call (one compile)
REF_STACK = 8


def reference(sinos: list[np.ndarray], cfg: dict, dtype: str = "float32") -> list[np.ndarray]:
    """The plain reconstruction of each frame, float64."""
    opts = cfg["stage"]["options"]
    if cfg["stage"]["processor"] == "mlem":
        return [mlem_ref(s, opts["n"], opts.get("mlem_iters", 4), dtype) for s in sinos]
    out = []
    for i in range(0, len(sinos), REF_STACK):
        chunk = sinos[i:i + REF_STACK]
        stack = np.stack(chunk + [chunk[-1]] * (REF_STACK - len(chunk)))
        out.extend(gridrec_ref(stack, opts["n"], dtype)[:len(chunk)])
    return out


# -- the comparison that decides ``correct`` -----------------------------------------


def _rel_l2(got, want: np.ndarray) -> float:
    if got is None or got.shape != want.shape or not np.isfinite(got).all():
        return math.inf
    g = np.asarray(got, np.float64)
    return float(np.linalg.norm(g - want) / np.linalg.norm(want))


def frame_rows(outputs: list, n_frames: int) -> list:
    """The reconstructions of a batch's frames, in message order, from the
    arrays its programs returned: a 2-D array is one frame, a 3-D one a
    stack whose first rows are the next frames (the rows past them are
    padding). The batch's frames share one shape, so one stack holds them
    all. Where the arrays do not account for each frame once, every frame
    reads None."""
    rows: list = []
    for out in outputs:
        need = n_frames - len(rows)
        if out is None or need <= 0:
            return [None] * n_frames
        rows.extend([out] if out.ndim == 2 else out[:need])
    return rows if len(rows) == n_frames else [None] * n_frames


def _readings(record, pool, kept, cfg, low: bool) -> dict:
    """Every real row of every kept batch against the reference on its
    frame, and the state (the last frame's reconstruction) too."""
    pairs = []  # (pool entry, what the stage made of it)
    for batch, state, outputs in kept:
        entries = [int(record.order[m]) for m in batch.messages]
        pairs.extend(zip(entries, frame_rows(outputs, len(entries))))
        pairs.append((entries[-1], state))
    used = sorted({e for e, _ in pairs})
    want = dict(zip(used, reference([pool[e] for e in used], cfg))) if used else {}
    if low and used:
        low_ref = dict(zip(used, reference([pool[e] for e in used], cfg, "bfloat16")))
        pairs = [(e, low_ref[e]) for e, _ in pairs]
    worst = max((_rel_l2(g, want[e]) for e, g in pairs), default=0.0)
    frames = sum(len(b.messages) for b, _, _ in kept)
    return {"recon_rel_l2": {"value": worst, "limit": cfg["check"]["recon_rel_l2"],
                             "pass_if": "<="},
            "frames_compared": {"value": frames, "limit": cfg["check"]["min_frames"],
                                "pass_if": ">="}}


def compare(record, pool, kept, cfg, seed) -> dict:
    """Largest relative L2 gap between a kept reconstruction (every frame
    of a kept batch, and its state) and the reference on the same frame;
    at least ``min_frames`` frames compared."""
    return _readings(record, pool, kept, cfg, low=False)


def control(record, pool, kept, cfg, seed) -> dict:
    """The same numbers with the bfloat16 reference in the program's place."""
    return _readings(record, pool, kept, cfg, low=True)


# -- work per frame, from shapes ---------------------------------------------------------


def work(cfg: dict) -> dict:
    sz = cfg["sizes"]
    a, d, n = sz["n_angles"], sz["n_det"], cfg["stage"]["options"]["n"]
    interp = a * n * n * OPS_PER_INTERP
    proj_bytes = 4 * (a * d + n * n)  # sinogram in, image out (or back)
    filt = a * (2 * 5 * d * math.log2(d) + 2 * d)
    iters = cfg["stage"]["options"].get("mlem_iters", 4)
    return {
        "bp_flops": interp, "bp_bytes": proj_bytes,
        "fp_flops": interp, "fp_bytes": proj_bytes,
        "gridrec_flops": filt + interp + n * n,
        "mlem_flops": (2 * iters + 1) * interp + iters * 2 * (a * d + n * n),
    }
