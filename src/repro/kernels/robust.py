"""Pallas TPU kernel: robust gossip displacement apply.

The robust mixing protocols (repro.api.robust: ``clipped_gossip`` /
``trimmed_gossip``) reduce to ONE elementwise pass over the flat ``[W, N]``
plane once the per-row statistics (norm-clip scale, trim threshold,
staleness-adaptive rate) are known:

    theta'[w, :] = theta[w, :] + scale[w] * delta[w, :] * (|delta[w, :]| <= thr[w])

where ``delta`` is the mixing displacement (mixed - local), ``scale`` folds
the norm-clip factor and the staleness-adaptive rate, and ``thr`` is the
coordinate-trim threshold (+inf disables trimming). The per-row reductions
that produce scale/thr are O(W) scalars off a single norm pass, so this apply
is the bandwidth-bound part — 3 streams, read theta/delta once, write theta'
once. Tiling/aliasing follows :mod:`repro.kernels.fused_update`: when tiles
cover N exactly the theta input aliases the output (in-place on the resident
buffers).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.fused_update import BLOCK, plane_call


def _robust_kernel(theta_ref, delta_ref, sc_ref, out_ref):
    t = theta_ref[...].astype(jnp.float32)
    d = delta_ref[...].astype(jnp.float32)
    sc = sc_ref[...]
    scale, thr = sc[:, 0:1], sc[:, 1:2]
    keep = (jnp.abs(d) <= thr).astype(jnp.float32)
    out_ref[...] = (t + scale * (d * keep)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def robust_flat_apply(theta, delta, scale, thr, *, block: int = BLOCK,
                      interpret: bool = False):
    """theta/delta: [W, N] flat buffers; scale/thr: scalar or [W] per-replica
    (traced OK — they ride in the scalar operand). Returns theta' [W, N], with
    theta aliased into the output (in-place) when the tiling covers N exactly.
    """
    (out,) = plane_call(_robust_kernel, [theta, delta], [scale, thr], (0,),
                        block=block, interpret=interpret)
    return out
