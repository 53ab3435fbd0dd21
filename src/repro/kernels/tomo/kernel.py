"""Pallas TPU kernels: parallel-beam forward/back projection.

Both projectors apply the linear-interpolation weights of ``ref.py``: pixel
p of an angle lands at detector coordinate s_p and splits between bins
floor(s_p) and floor(s_p) + 1, which is the hat function

    w(p, d) = max(0, 1 - |d - s_p|).

Both run on the VPU in exact f32, with no matmul and so no MXU precision to
choose.

* backproject: one vreg holds a 32 x 32 pixel block, sublane i and lane j
  being pixel (4i + j // 32, j % 32) of the block. At one angle the 2 x 2
  blocks of a 64 x 64 quad span at most 63 (|cos| + |sin|) + 2 <= 92
  detector bins, so the taps of all four lie in one 128-bin row of the
  sinogram laid out as overlapping rows ``chunks[a, k] = sino[a, 32k : 32k +
  128]`` (zero-padded so that every tap of every pixel exists; the zeros
  stand for the bins outside [0, n_det) that the reference drops). Per vreg
  and angle the kernel gathers the two taps floor(s_p) and floor(s_p) + 1
  from the lanes of its quad's row (``tpu.dynamic_gather``) and adds
  ``(1 - f) g0 + f g1``. A grid step works on a row of quads as whole
  arrays, so the kernel traces to a few operations per quad. The
  (n/32, n/32, 8, 128) blocks go back to (n, n) in XLA.
* project: the image is cut into (by, 128) pixel tiles, one lane per column
  (by = 64 by default: on a v5e at 360 x 1448 -> n = 1448 it beat by = 32).
  For one angle, a tile's s_p span at most hypot(128, by) + 2 detector
  bins, so a tile only visits the bins of that window: for each bin d the
  tile's weighted sum is reduced over its rows into one (1, 128) row of a
  VMEM window scratch. After the window, the scratch is transposed and
  summed over lanes, 128 bins at a time, into the (A, n_det / 128, 128)
  output, whose lane-chunk axis is untiled so the chunk index may be
  dynamic. Bins outside [0, n_det) are never visited.

cos/sin arrive by scalar prefetch. Images are zero-padded to whole blocks
or tiles and cropped after; padded pixels add nothing to a projection.

Grids: backproject (quad row, quad-column group, angle block) with the
output blocks revisited across angle blocks; project (angle block, row
block, column block) with the angle block's sinogram rows revisited across
tiles. The accumulating axes come last, so the sequential grid makes the
accumulation race-free.
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


BLOCK = 32  # pixels on a side of the block one vreg holds
QUAD = 2 * BLOCK  # pixels on a side of the 2 x 2 blocks that share one sinogram row
STRIDE = 32  # detector bins between the starts of two sinogram rows
SUBLANES = 8
MARGIN = 2  # bins the scalar lowest-bin estimate is pushed down by, against rounding
# quads a backprojection grid step adds into, along a row of them. A longer
# loop body overlaps more gathers (v5e at 360 x 1448 -> n = 1448: 8.9 ms at
# 46 blocks a step, 8.1 ms at 92), but each quad adds to the Python time of
# tracing and lowering the kernel, which set-up pays for every program.
STEP_MAX_QUADS = 24


def _block_offsets():
    """(row, col) offsets inside a 32 x 32 block of each vreg element."""
    shape = (SUBLANES, LANES)
    i = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    j = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return (LANES // BLOCK) * i + j // BLOCK, j % BLOCK


def _bp_kernel(cos_ref, sin_ref, chunks_ref, out_ref, *, n, n_det, pad, nq, ba):
    """Adds ``ba`` angles into one row of ``nq`` quads: out_ref[r] holds
    block row r of the quads, (2 nq blocks x 8 sublanes, 128)."""
    gr, gc, ab = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(ab == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    c = (n - 1) / 2.0
    mid = (n_det - 1) / 2.0
    cb = 2 * nq  # blocks along the row
    yo, xo = _block_offsets()
    row0, col0 = gr * QUAD, gc * (nq * QUAD)
    # centered coordinates, exact in f32: x of every block of the row, y of
    # each of the two block rows, and each quad's top-left corner
    q = jax.lax.broadcasted_iota(jnp.int32, (cb, SUBLANES, LANES), 0)
    x = (col0 + BLOCK * q + xo).astype(jnp.float32) - c
    ys = [(row0 + BLOCK * r + yo).astype(jnp.float32) - c for r in range(2)]
    x0 = [(col0 + QUAD * p).astype(jnp.float32) - c for p in range(nq)]
    y0 = row0.astype(jnp.float32) - c

    def angle(i, carry):
        ct, st = cos_ref[ab * ba + i], sin_ref[ab * ba + i]
        # a quad's lowest s_p is its corner's x ct plus this, in padded bins
        # and less a margin
        low = (jnp.minimum((QUAD - 1) * ct, 0.0) + jnp.minimum((QUAD - 1) * st, 0.0)
               + y0 * st + (mid + pad - MARGIN))
        rows, offs = [], []
        for p in range(nq):
            # the padding keeps the estimate >= 0, so truncation floors
            k = ((x0[p] * ct + low) * (1.0 / STRIDE)).astype(jnp.int32)
            rows.append(jnp.broadcast_to(chunks_ref[i, pl.ds(k, 1), :], (2, SUBLANES, LANES)))
            offs.append(jnp.full((2, SUBLANES, LANES), pad - STRIDE * k, jnp.int32))
        # each block's row is its quad's; an index into it is a padded bin less off
        row = jnp.concatenate(rows).reshape(cb * SUBLANES, LANES)
        off = jnp.concatenate(offs).reshape(cb * SUBLANES, LANES)
        xct = x * ct
        for r in range(2):
            s = (xct + ys[r] * st + mid).reshape(cb * SUBLANES, LANES)  # ref.py's s
            s0 = jnp.floor(s)
            f = s - s0
            i0 = s0.astype(jnp.int32) + off  # in [0, 124): both taps lie in the row
            g0 = jnp.take_along_axis(row, i0, axis=1, mode="promise_in_bounds")
            g1 = jnp.take_along_axis(row, i0 + 1, axis=1, mode="promise_in_bounds")
            out_ref[r] += (1.0 - f) * g0 + f * g1
        return carry

    jax.lax.fori_loop(0, ba, angle, 0)


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


def _bp_sinogram_rows(sino, a_pad: int, n_det: int, reach: float):
    """Zero-pad the detector axis so that the taps of every pixel within
    ``reach`` of the image center exist, and lay it out as overlapping rows
    ``chunks[a, k] = sino_p[a, 32k : 32k + 128]``. Returns (chunks, pad):
    bin d of the sinogram is bin d + pad of the padded axis."""
    mid = (n_det - 1) / 2.0
    pad = max(0, math.ceil(reach - mid)) + MARGIN + 2
    k_rows = int((mid + reach + pad) // STRIDE) + 1
    per_row = LANES // STRIDE
    width = STRIDE * (k_rows + per_row - 1)
    a = sino.shape[0]
    sino_p = jnp.pad(sino.astype(jnp.float32),
                     ((0, a_pad - a), (pad, max(0, width - pad - n_det))))[:, :width]
    pieces = sino_p.reshape(a_pad, k_rows + per_row - 1, STRIDE)
    return jnp.concatenate([pieces[:, j:j + k_rows] for j in range(per_row)], axis=-1), pad


@functools.partial(jax.jit, static_argnames=("n", "ba", "interpret"))
def backproject_pallas(sino, cos_t, sin_t, *, n: int, ba: int = 8, interpret: bool = False):
    """sino (A, n_det), cos/sin (A,) -> image (n, n)."""
    a, n_det = sino.shape
    quads = _round_up(n, QUAD) // QUAD
    nq = -(-quads // -(-quads // STEP_MAX_QUADS))  # the fewest padded quads
    nbr, nbc = 2 * quads, 2 * _round_up(quads, nq)  # blocks, padded
    cos_p, sin_p = _pad_angles(cos_t, sin_t, ba)
    a_pad = cos_p.shape[0]
    # farthest padded pixel from the center, on either axis, times sqrt(2)
    c = (n - 1) / 2.0
    reach = max(c, BLOCK * max(nbr, nbc) - 1 - c) * math.sqrt(2.0) + 1.0
    chunks, pad = _bp_sinogram_rows(sino, a_pad, n_det, reach)
    k_rows = chunks.shape[1]
    kernel = functools.partial(_bp_kernel, n=n, n_det=n_det, pad=pad, nq=nq, ba=ba)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nbr // 2, nbc // (2 * nq), a_pad // ba),
            in_specs=[pl.BlockSpec((ba, k_rows, LANES), lambda gr, gc, ab, *_: (ab, 0, 0))],
            out_specs=pl.BlockSpec((2, 2 * nq * SUBLANES, LANES),
                                   lambda gr, gc, ab, *_: (gr, gc, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((nbr, nbc * SUBLANES, LANES), jnp.float32),
        interpret=interpret,
    )(cos_p, sin_p, chunks)
    # vreg (i, j) of block (br, bc) is pixel (32 br + 4i + j // 32, 32 bc + j % 32)
    img = out.reshape(nbr, nbc, BLOCK, BLOCK).transpose(0, 2, 1, 3)
    return img.reshape(nbr * BLOCK, nbc * BLOCK)[:n, :n]


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
