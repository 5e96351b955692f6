"""Pallas TPU kernels: gossip-compression codecs on the flat parameter plane.

The wire cost of a gossip round is the size of the flat replica buffer that
rides the collective permute (repro.core.gossip_dist). These kernels shrink
that buffer before it leaves the chip and reconstruct it on arrival:

- ``q8`` — stochastic-rounding int8 quantization with one float32 scale per
  ``block`` elements (~4x fewer wire bytes for float32 planes);
- ``topk`` — per-block magnitude top-k selection with an error-feedback
  residual (the untransmitted mass is carried to the next round), wire cost
  8 bytes per kept element.

Layout follows :mod:`repro.kernels.fused_update`: a ``[W, N]`` replica
buffer from :mod:`repro.common.flat` is cut into ``(rows, per * block)``
tiles — the tile's replica rows by ``per`` whole codec blocks — and the
kernels unroll over the ``per`` codec blocks of a tile. Per-block outputs
(q8 scales, top-k values and indices) leave the kernel as ``[G, W, per*]``
tiles (G tiles per row), a block whose last two dims are whole, and are
laid back out as ``[W, nb*]`` outside it. The last tile of a row may be
ragged: every codec block depends only on its own elements, and the
out-of-range part of an edge tile is never written back. Rounding noise comes from
:func:`repro.kernels.ref.stochastic_uniform` — a deterministic hash of
(per-row seed, in-row element index) — so the kernels are bit-identical to
the jnp oracles in :mod:`repro.kernels.ref` (the parity target in
tests/test_comm.py) and both engines produce the same wire payload from the
same (round, worker) seed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_update import tile_cols_cap, tile_rows
from repro.kernels.ref import _pad_to_blocks, stochastic_uniform

MAX_BLOCKS = 64   # codec blocks per tile (the kernels unroll over them)


def _blocked(x, block: int):
    """[W, N] -> ([W, nb*block] zero-padded, nb) — same padding rule as the
    oracles (shared helper keeps kernel and oracle layouts in lockstep)."""
    xb, nb = _pad_to_blocks(x, block)
    return xb.reshape(x.shape[0], nb * block), nb


def _tiling(W: int, nb: int, block: int):
    """(rows, per, G): replica rows and codec blocks per tile, tiles per row."""
    rows = tile_rows(W)
    per = max(1, min(nb, MAX_BLOCKS, tile_cols_cap(rows) // block))
    return rows, per, -(-nb // per)


def _plane_spec(rows: int, cols: int):
    return pl.BlockSpec((rows, cols), lambda i, j: (i, j))


def _tile_spec(rows: int, cols: int):
    """Block of a [G, W, cols] per-tile output: tile j, replica rows i."""
    return pl.BlockSpec((None, rows, cols), lambda i, j: (j, i, 0))


def _to_tiles(x, G: int, width: int):
    """[W, m] per-block array -> [G, W, width] tiles (zero-padded)."""
    W, m = x.shape
    if G * width != m:
        x = jnp.pad(x, ((0, 0), (0, G * width - m)))
    return x.reshape(W, G, width).transpose(1, 0, 2)


def _from_tiles(x, m: int):
    """Inverse of :func:`_to_tiles`: [G, W, width] -> [W, m]."""
    G, W, width = x.shape
    return x.transpose(1, 0, 2).reshape(W, G * width)[:, :m]


def _pick_col(x, col, c):
    """Column ``c`` of a (rows, width) tile as a (rows, 1) column (exact: a
    masked sum of one element and zeros)."""
    return jnp.sum(jnp.where(col == c, x, jnp.zeros_like(x)), axis=1,
                   keepdims=True)


# ---------------------------------------------------------------------------
# q8: stochastic-rounding int8 quantization, per-block scales
# ---------------------------------------------------------------------------

def _q8_encode_kernel(x_ref, seed_ref, v_ref, s_ref, *, block: int, per: int):
    rows = x_ref.shape[0]
    seed = seed_ref[...]                                     # (rows, 1)
    base = pl.program_id(1) * (per * block)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, block), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, per), 1)
    scales = jnp.zeros((rows, per), jnp.float32)
    for t in range(per):
        sl = slice(t * block, (t + 1) * block)
        x = x_ref[:, sl].astype(jnp.float32)
        amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
        # multiply, not divide: keeps the scale bit-identical to the oracle
        # under every lowering (XLA folds /const into *reciprocal
        # inconsistently)
        scale = jnp.where(amax > 0, amax * jnp.float32(1.0 / 127.0), 1.0)
        idx = (base + t * block + lane).astype(jnp.uint32)
        u = stochastic_uniform(idx, seed)
        q = jnp.clip(jnp.floor(x / scale + u), -127.0, 127.0)
        v_ref[:, sl] = q.astype(jnp.int8)
        scales = jnp.where(col == t, scale, scales)
    s_ref[...] = scales


def _q8_decode_kernel(v_ref, s_ref, out_ref, *, block: int, per: int):
    rows = v_ref.shape[0]
    s = s_ref[...]
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, per), 1)
    for t in range(per):
        sl = slice(t * block, (t + 1) * block)
        out_ref[:, sl] = v_ref[:, sl].astype(jnp.float32) * _pick_col(s, col, t)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def q8_encode(buf, seeds, *, block: int, interpret: bool = False):
    """buf: [W, N] float plane bucket; seeds: [W] uint32 per-row rounding
    seeds. Returns (values int8 [W, nb*block], scales f32 [W, nb])."""
    W, n = buf.shape
    xf, nb = _blocked(buf.astype(jnp.float32), block)
    rows, per, G = _tiling(W, nb, block)
    sd = seeds.astype(jnp.uint32).reshape(W, 1)
    spec = _plane_spec(rows, per * block)
    values, scales = pl.pallas_call(
        functools.partial(_q8_encode_kernel, block=block, per=per),
        grid=(W // rows, G),
        in_specs=[spec, _plane_spec(rows, 1)],
        out_specs=[spec, _tile_spec(rows, per)],
        out_shape=[jax.ShapeDtypeStruct((W, nb * block), jnp.int8),
                   jax.ShapeDtypeStruct((G, W, per), jnp.float32)],
        interpret=interpret,
    )(xf, sd)
    return values, _from_tiles(scales, nb)


@functools.partial(jax.jit, static_argnames=("n", "block", "interpret"))
def q8_decode(values, scales, *, n: int, block: int, interpret: bool = False):
    """(values int8 [W, nb*block], scales f32 [W, nb]) -> [W, n] float32."""
    W, nbb = values.shape
    nb = nbb // block
    rows, per, G = _tiling(W, nb, block)
    spec = _plane_spec(rows, per * block)
    out = pl.pallas_call(
        functools.partial(_q8_decode_kernel, block=block, per=per),
        grid=(W // rows, G),
        in_specs=[spec, _tile_spec(rows, per)],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((W, nb * block), jnp.float32),
        interpret=interpret,
    )(values, _to_tiles(scales, G, per))
    return out[:, :n]


# ---------------------------------------------------------------------------
# topk: per-block magnitude top-k + error-feedback residual
# ---------------------------------------------------------------------------

def _topk_encode_kernel(x_ref, r_ref, v_ref, i_ref, res_ref, *, k: int,
                        block: int, per: int):
    rows = x_ref.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, block), 1)
    kcol = jax.lax.broadcasted_iota(jnp.int32, (rows, per * k), 1)
    vals = jnp.zeros((rows, per * k), jnp.float32)
    idxs = jnp.zeros((rows, per * k), jnp.int32)
    for t in range(per):
        sl = slice(t * block, (t + 1) * block)
        acc = x_ref[:, sl].astype(jnp.float32) + r_ref[:, sl]

        def pick(j, carry, acc=acc, t=t):
            vals, idxs, mag = carry
            # taken entries sit at -1, below any |acc|, so they can't be
            # re-selected; ties on magnitude resolve to the lowest index
            # (matches lax.top_k)
            m = jnp.max(mag, axis=1, keepdims=True)
            sel = jnp.min(jnp.where(mag == m, lane, block), axis=1, keepdims=True)
            hit = lane == sel
            v = jnp.sum(jnp.where(hit, acc, 0.0), axis=1, keepdims=True)
            here = kcol == t * k + j
            return (jnp.where(here, v, vals), jnp.where(here, sel, idxs),
                    jnp.where(hit, -1.0, mag))

        vals, idxs, mag = jax.lax.fori_loop(0, k, pick,
                                            (vals, idxs, jnp.abs(acc)))
        res_ref[:, sl] = jnp.where(mag < 0, 0.0, acc)
    v_ref[...] = vals
    i_ref[...] = idxs


def _topk_decode_kernel(v_ref, i_ref, out_ref, *, k: int, block: int, per: int):
    rows = v_ref.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, block), 1)
    kcol = jax.lax.broadcasted_iota(jnp.int32, (rows, per * k), 1)
    vals, idxs = v_ref[...], i_ref[...]
    for t in range(per):
        def scatter(j, dense, t=t):
            c = t * k + j
            sel = _pick_col(idxs, kcol, c)
            return dense + jnp.where(lane == sel, _pick_col(vals, kcol, c), 0.0)

        out_ref[:, t * block:(t + 1) * block] = jax.lax.fori_loop(
            0, k, scatter, jnp.zeros((rows, block), jnp.float32))


@functools.partial(jax.jit, static_argnames=("k", "block", "interpret"))
def topk_encode(buf, residual, *, k: int, block: int, interpret: bool = False):
    """buf: [W, N] float bucket; residual: [W, N] f32 error-feedback carry.
    Returns (values f32 [W, nb*k], local indices int32 [W, nb*k],
    residual' f32 [W, N])."""
    W, n = buf.shape
    xf, nb = _blocked(buf.astype(jnp.float32), block)
    rf, _ = _blocked(residual.astype(jnp.float32), block)
    rows, per, G = _tiling(W, nb, block)
    spec = _plane_spec(rows, per * block)
    kspec = _tile_spec(rows, per * k)
    vals, idxs, res = pl.pallas_call(
        functools.partial(_topk_encode_kernel, k=k, block=block, per=per),
        grid=(W // rows, G),
        in_specs=[spec, spec],
        out_specs=[kspec, kspec, spec],
        out_shape=[jax.ShapeDtypeStruct((G, W, per * k), jnp.float32),
                   jax.ShapeDtypeStruct((G, W, per * k), jnp.int32),
                   jax.ShapeDtypeStruct((W, nb * block), jnp.float32)],
        interpret=interpret,
    )(xf, rf)
    return _from_tiles(vals, nb * k), _from_tiles(idxs, nb * k), res[:, :n]


@functools.partial(jax.jit, static_argnames=("n", "k", "block", "interpret"))
def topk_decode(values, idx, *, n: int, k: int, block: int, interpret: bool = False):
    """(values f32 [W, nb*k], indices int32 [W, nb*k]) -> [W, n] float32."""
    W, m = values.shape
    nb = m // k
    rows, per, G = _tiling(W, nb, block)
    kspec = _tile_spec(rows, per * k)
    out = pl.pallas_call(
        functools.partial(_topk_decode_kernel, k=k, block=block, per=per),
        grid=(W // rows, G),
        in_specs=[kspec, kspec],
        out_specs=_plane_spec(rows, per * block),
        out_shape=jax.ShapeDtypeStruct((W, nb * block), jnp.float32),
        interpret=interpret,
    )(_to_tiles(values, G, per * k), _to_tiles(idx, G, per * k))
    return out[:, :n]
