"""Pallas TPU kernels: parallel-beam forward/back projection.

Both projectors apply the linear-interpolation weights of ``ref.py``: pixel
p of an angle lands at detector coordinate s_p and splits between bins
floor(s_p) and floor(s_p) + 1, which is the hat function

    w(p, d) = max(0, 1 - |d - s_p|).

GPU codes scatter or gather per ray; a TPU has neither in its vector unit.
Here both projectors are elementwise hat evaluations on the VPU in exact
f32, with no matmul and so no MXU precision to choose:

* the image is cut into (by, 128) pixel tiles, one lane per column
  (by = 64 by default: on a v5e at 360 x 1448 -> n = 1448 it beat by = 32
  in both projectors). For one angle, a tile's s_p span at most
  hypot(128, by) + 2 detector bins, so a tile only visits the bins of that
  window, not all n_det of them;
* backproject: for each bin d of the window, ``tile += w(., d) * sino[a, d]``
  with sino[a, d] a scalar read from SMEM (the angle block is DMA'd there);
* project: for each bin d, the tile's weighted sum is reduced over its rows
  into one (1, 128) row of a VMEM window scratch. After the window, the
  scratch is transposed and summed over lanes, 128 bins at a time, into the
  (A, n_det / 128, 128) output, whose lane-chunk axis is untiled so the
  chunk index may be dynamic.

cos/sin arrive by scalar prefetch. The image is zero-padded to whole tiles
and the detector to whole 128-lane chunks; padded pixels add nothing to a
projection and padded rows and columns are cropped. Bins outside
[0, n_det) are never visited, matching the reference, which drops them.

Grids: backproject (row block, column block, angle block) with the image
tile revisited across angle blocks; project (angle block, row block, column
block) with the angle block's sinogram rows revisited across tiles. The
accumulating axes come last, so the sequential grid makes the accumulation
race-free.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _coords(n: int, by: int, rb, cb):
    """Centered (x, y) pixel coordinates of tile (rb, cb), each (by, LANES)."""
    c = (n - 1) / 2.0
    shape = (by, LANES)
    y = (rb * by + jax.lax.broadcasted_iota(jnp.int32, shape, 0)).astype(jnp.float32) - c
    x = (cb * LANES + jax.lax.broadcasted_iota(jnp.int32, shape, 1)).astype(jnp.float32) - c
    return x, y


def _window(n: int, n_det: int, by: int, rb, cb, ct, st):
    """Detector bins [lo, hi) that tile (rb, cb) touches at angle (ct, st),
    clipped to [0, n_det). Scalar math on the tile's corners."""
    c = (n - 1) / 2.0
    base = ((cb * LANES).astype(jnp.float32) - c) * ct \
        + ((rb * by).astype(jnp.float32) - c) * st + (n_det - 1) / 2.0
    ex = (LANES - 1) * ct
    ey = (by - 1) * st
    s_min = base + jnp.minimum(ex, 0.0) + jnp.minimum(ey, 0.0)
    s_max = base + jnp.maximum(ex, 0.0) + jnp.maximum(ey, 0.0)
    lo = jnp.maximum(jnp.floor(s_min).astype(jnp.int32), 0)
    hi = jnp.minimum(jnp.floor(s_max).astype(jnp.int32) + 2, n_det)
    return lo, hi


def _hat(s, d):
    return jnp.maximum(1.0 - jnp.abs(s - d.astype(jnp.float32)), 0.0)


def _bp_kernel(cos_ref, sin_ref, sino_ref, out_ref, *, n, n_det, by, ba):
    rb, cb, ab = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(ab == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x, y = _coords(n, by, rb, cb)

    def angle(i, acc):
        ct, st = cos_ref[ab * ba + i], sin_ref[ab * ba + i]
        s = x * ct + y * st + (n_det - 1) / 2.0
        lo, hi = _window(n, n_det, by, rb, cb, ct, st)
        return jax.lax.fori_loop(
            lo, hi, lambda d, acc: acc + _hat(s, d) * sino_ref[i, d], acc)

    out_ref[...] += jax.lax.fori_loop(0, ba, angle, jnp.zeros((by, LANES), jnp.float32))


def _fp_kernel(cos_ref, sin_ref, img_ref, out_ref, win_ref, *, n, n_det, by, ba):
    ab, rb, cb = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_chunks = out_ref.shape[1]

    @pl.when((rb == 0) & (cb == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x, y = _coords(n, by, rb, cb)
    img = img_ref[...].astype(jnp.float32)

    def angle(i, carry):
        ct, st = cos_ref[ab * ba + i], sin_ref[ab * ba + i]
        s = x * ct + y * st + (n_det - 1) / 2.0
        lo, hi = _window(n, n_det, by, rb, cb, ct, st)
        chunk0 = lo // LANES
        win_ref[...] = jnp.zeros_like(win_ref)

        def bin_(d, carry):
            row = jnp.sum(_hat(s, d) * img, axis=0, keepdims=True)  # (1, LANES)
            win_ref[pl.ds(d - chunk0 * LANES, 1), :] = row
            return carry

        jax.lax.fori_loop(lo, hi, bin_, 0)
        for j in range(win_ref.shape[0] // LANES):
            @pl.when(chunk0 + j < n_chunks)
            def _flush():
                blk = win_ref[j * LANES:(j + 1) * LANES, :]  # (bins, pixels)
                out_ref[i, pl.ds(chunk0 + j, 1), :] += jnp.sum(blk.T, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, ba, angle, 0)


def _window_rows(by: int) -> int:
    """Scratch rows for one tile's bin window: the span plus the offset of
    its first bin inside its 128-lane chunk."""
    span = math.ceil(math.hypot(LANES - 1, by - 1)) + 3
    return _round_up(LANES - 1 + span, LANES)


def _pad_angles(cos_t, sin_t, ba: int):
    a_pad = _round_up(cos_t.shape[0], ba)
    pad = (0, a_pad - cos_t.shape[0])
    return jnp.pad(cos_t.astype(jnp.float32), pad), jnp.pad(sin_t.astype(jnp.float32), pad)


@functools.partial(jax.jit, static_argnames=("n", "by", "ba", "interpret"))
def backproject_pallas(sino, cos_t, sin_t, *, n: int, by: int = 64, ba: int = 8,
                       interpret: bool = False):
    """sino (A, n_det), cos/sin (A,) -> image (n, n)."""
    a, n_det = sino.shape
    cos_p, sin_p = _pad_angles(cos_t, sin_t, ba)
    a_pad = cos_p.shape[0]
    nr, nc = _round_up(n, by), _round_up(n, LANES)
    sino_p = jnp.pad(sino.astype(jnp.float32), ((0, a_pad - a), (0, 0)))
    kernel = functools.partial(_bp_kernel, n=n, n_det=n_det, by=by, ba=ba)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nr // by, nc // LANES, a_pad // ba),
            in_specs=[pl.BlockSpec((ba, n_det), lambda rb, cb, ab, *_: (ab, 0),
                                   memory_space=pltpu.SMEM)],
            out_specs=pl.BlockSpec((by, LANES), lambda rb, cb, ab, *_: (rb, cb)),
        ),
        out_shape=jax.ShapeDtypeStruct((nr, nc), jnp.float32),
        interpret=interpret,
    )(cos_p, sin_p, sino_p)
    return out[:n, :n]


@functools.partial(jax.jit, static_argnames=("n_det", "by", "ba", "interpret"))
def project_pallas(img, cos_t, sin_t, *, n_det: int, by: int = 64, ba: int = 8,
                   interpret: bool = False):
    """img (n, n), cos/sin (A,) -> sinogram (A, n_det)."""
    n = img.shape[0]
    a = cos_t.shape[0]
    cos_p, sin_p = _pad_angles(cos_t, sin_t, ba)
    a_pad = cos_p.shape[0]
    nr, nc = _round_up(n, by), _round_up(n, LANES)
    n_chunks = _round_up(n_det, LANES) // LANES
    img_p = jnp.pad(img.astype(jnp.float32), ((0, nr - n), (0, nc - n)))
    kernel = functools.partial(_fp_kernel, n=n, n_det=n_det, by=by, ba=ba)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(a_pad // ba, nr // by, nc // LANES),
            in_specs=[pl.BlockSpec((by, LANES), lambda ab, rb, cb, *_: (rb, cb))],
            out_specs=pl.BlockSpec((ba, n_chunks, LANES), lambda ab, rb, cb, *_: (ab, 0, 0)),
            scratch_shapes=[pltpu.VMEM((_window_rows(by), LANES), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((a_pad, n_chunks, LANES), jnp.float32),
        interpret=interpret,
    )(cos_p, sin_p, img_p)
    return out.reshape(a_pad, n_chunks * LANES)[:a, :n_det]
