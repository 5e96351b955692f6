"""Pallas TPU kernel: fused elastic-gossip + NAG parameter update.

A gossip round touches every parameter byte of the replica shard. Unfused,
XLA emits separate sweeps for the velocity update, the elastic move, and the
parameter update — >=5 HBM reads + 2 writes per element. This kernel does one
pass: read theta/peer/v/g once, write theta'/v' once (6 streams total), at
arithmetic intensity ~0.5 flop/byte — pure bandwidth, so fusion is the whole
game (byte accounting: benchmarks/fused_step.py).

Tiling (:func:`plane_call`): a ``[W, N]`` plane is cut into column tiles of a
lane-multiple width chosen by :func:`_tile`. With several replicas a tile is
``(rows, cols)`` with ``rows`` the whole replica dim (8 of it when W is a
multiple of 8 above 8) — the tiling XLA itself gives a ``[W, N]`` f32 array
on the TPU (``T(W,128)``), so the kernel reads the resident buffer with no
relayout copy. Per-replica scalars (coef, eta, mu) ride
in a small ``[W, k]`` f32 operand whose block is the tile's rows, so traced
values never retrigger compilation and the kernel body is branch-free.

Two entry points:

- :func:`fused_flat_elastic_nag_update` / :func:`fused_flat_nag_update` —
  ``[W, N]`` flat replica buffers from :mod:`repro.common.flat`, with
  per-replica coef and traced eta/mu. These are what the engines call
  (through :mod:`repro.kernels.ops`).
- :func:`fused_elastic_nag_update` — any single array, seen as a one-row
  plane (kept for the oracle tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 65536          # max elements per replica row in one tile
LANE = 128             # lane width (elements): flat-plane totals are multiples of it
SUBLANE = 8            # f32 sublanes per vreg
MIN_TILE = SUBLANE * LANE   # one full f32 (sublane x lane) register tile
TILE_BYTES = 1 << 20   # VMEM one stream's tile may take: 6 streams x 2 buffers < 16 MiB


def _tile(n: int, block: int):
    """(block', nblocks, padded) tiling of an ``n``-element plane row.

    The flat-resident plane makes in-place updates possible: when the tiles
    cover ``n`` exactly, the kernel inputs alias the outputs
    (``input_output_aliases``) and no pad copy is made — theta/v update in
    place. ``n <= block`` collapses to one exact tile; larger planes tile at
    ``block`` when it divides ``n``, else at the largest lane-multiple
    divisor of ``n`` that fits (flat totals are always lane multiples, so one
    exists; e.g. n = 925*128 tiles at 185*128). Only when every exact tile
    would be degenerate (< one sublane x lane register tile, or below an
    explicitly smaller caller block) does it fall back to the padded,
    non-aliased layout.
    """
    if n == 0 or n <= block:
        return max(n, 1), 1, n == 0
    if n % block == 0:
        return block, n // block, False
    if n % LANE == 0:
        floor = min(MIN_TILE, block)
        m, cap = n // LANE, block // LANE
        for d in range(cap, 0, -1):
            if m % d == 0 and d * LANE >= floor:
                return d * LANE, n // (d * LANE), False
    return block, (n + block - 1) // block, True


def tile_rows(W: int) -> int:
    """Replica rows per tile: all of them, or 8 when W is a multiple of 8."""
    return SUBLANE if W > SUBLANE and W % SUBLANE == 0 else W


def tile_cols_cap(rows: int) -> int:
    """Widest lane-multiple column tile whose f32 VMEM buffer (rows padded
    to whole sublane groups) stays within ``TILE_BYTES``."""
    sub = -(-rows // SUBLANE) * SUBLANE
    return max(LANE, TILE_BYTES // (4 * sub) // LANE * LANE)


def _pad_blocks(x, n: int, nblocks: int, block: int):
    pad = nblocks * block - n
    return jnp.pad(x, ((0, 0), (0, pad))) if pad else x


def _scalar_rows(W: int, *cols) -> jnp.ndarray:
    """[W, len(cols)] f32: each col a python/traced scalar or a [W] vector."""
    rows = [jnp.broadcast_to(jnp.asarray(c, jnp.float32).reshape(-1), (W,))
            for c in cols]
    return jnp.stack(rows, axis=1)


def plane_call(kernel, planes, scalars, out_from, *, block: int,
               interpret: bool):
    """Run an elementwise ``kernel`` over same-shape ``[W, N]`` planes.

    ``kernel(*plane_refs, sc_ref, *out_refs)`` sees one tile of every plane
    and the tile's rows of the ``[W, len(scalars)]`` f32 scalar operand
    (each scalar a python/traced value or a ``[W]`` vector; column ``c`` of
    ``sc_ref[...]`` is scalar ``c`` as a per-row column). Output ``o`` has
    the shape and dtype of ``planes[out_from[o]]`` and aliases it whenever
    the tiling covers N exactly. Returns the list of ``[W, N]`` outputs.
    """
    W, n = planes[0].shape
    rows = tile_rows(W)
    bc, nb, padded = _tile(n, min(block, tile_cols_cap(rows)))
    xs = [_pad_blocks(x, n, nb, bc) for x in planes]
    sc = _scalar_rows(W, *scalars)
    spec = pl.BlockSpec((rows, bc), lambda i, j: (i, j))
    outs = pl.pallas_call(
        kernel,
        grid=(W // rows, nb),
        in_specs=[spec] * len(xs) + [pl.BlockSpec((rows, sc.shape[1]),
                                                  lambda i, j: (i, 0))],
        out_specs=[spec] * len(out_from),
        out_shape=[jax.ShapeDtypeStruct(xs[i].shape, xs[i].dtype)
                   for i in out_from],
        input_output_aliases={} if padded else {i: o for o, i in enumerate(out_from)},
        interpret=interpret,
    )(*xs, sc)
    return [o[:, :n] for o in outs]


def _flat_kernel(theta_ref, peer_ref, v_ref, g_ref, sc_ref,
                 theta_out_ref, v_out_ref):
    t = theta_ref[...].astype(jnp.float32)
    p = peer_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    sc = sc_ref[...]
    coef, eta, mu = sc[:, 0:1], sc[:, 1:2], sc[:, 2:3]
    v_new = mu * v - eta * g
    t_new = t - coef * (t - p) - eta * g + mu * v_new
    theta_out_ref[...] = t_new.astype(theta_out_ref.dtype)
    v_out_ref[...] = v_new.astype(v_out_ref.dtype)


def _flat_nag_kernel(theta_ref, v_ref, g_ref, sc_ref, theta_out_ref, v_out_ref):
    t = theta_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    sc = sc_ref[...]
    eta, mu = sc[:, 0:1], sc[:, 1:2]
    v_new = mu * v - eta * g
    theta_out_ref[...] = (t - eta * g + mu * v_new).astype(theta_out_ref.dtype)
    v_out_ref[...] = v_new.astype(v_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def fused_flat_elastic_nag_update(theta, peer, v, g, coef, eta, mu, *,
                                  block: int = BLOCK, interpret: bool = False):
    """Whole-plane fused update (paper Alg. 5 lines 3/7/9, simultaneous).

    theta/peer/v/g: [W, N] flat replica buffers (repro.common.flat layout);
    coef: scalar or [W] per-replica moving rate * participation gate;
    eta/mu: scalars (traced values OK — they ride in the scalar operand, so
    lr schedules don't retrigger compilation). Returns (theta', v') [W, N] —
    when the tiling covers N exactly the theta and v inputs are ALIASED to
    the outputs, so donated resident buffers update truly in place (no
    double HBM residency).
    """
    t_new, v_new = plane_call(_flat_kernel, [theta, peer, v, g],
                              [coef, eta, mu], (0, 2), block=block,
                              interpret=interpret)
    return t_new, v_new


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def fused_flat_nag_update(theta, v, g, eta, mu, *,
                          block: int = BLOCK, interpret: bool = False):
    """Pure-NAG whole-plane update (no peer stream): the non-communicating
    step of pairwise protocols. theta/v/g: [W, N]; eta/mu scalars (traced OK).
    Returns (theta', v'), with theta/v aliased into the outputs (in-place)
    whenever the tiling covers N exactly — see
    :func:`fused_flat_elastic_nag_update`."""
    t_new, v_new = plane_call(_flat_nag_kernel, [theta, v, g], [eta, mu],
                              (0, 1), block=block, interpret=interpret)
    return t_new, v_new


@functools.partial(jax.jit, static_argnames=("eta", "mu", "block", "interpret"))
def fused_elastic_nag_update(theta, peer, v, g, coef_gate, *, eta: float, mu: float,
                             block: int = BLOCK, interpret: bool = False):
    """theta/peer/v/g: same-shape arrays (any rank); coef_gate: scalar f32
    (= alpha * participation gate). Returns (theta', v') — the flat kernel on
    the arrays seen as one-row planes."""
    row = lambda x: x.reshape(1, -1)
    t_new, v_new = fused_flat_elastic_nag_update(
        row(theta), row(peer), row(v), row(g), coef_gate, eta, mu,
        block=block, interpret=interpret)
    return t_new.reshape(theta.shape), v_new.reshape(v.shape)
