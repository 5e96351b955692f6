"""Flat parameter plane: one contiguous lane-aligned buffer per dtype.

Every per-leaf sweep of a parameter pytree costs one kernel launch and one
HBM round trip per leaf, and every per-leaf collective costs one ppermute per
leaf. Flattening the tree into a single padded buffer per dtype makes the hot
loop's cost independent of the tree's shape: the fused Pallas update
(:mod:`repro.kernels.fused_update`) becomes ONE bandwidth-bound pass and the
distributed gossip exchange (:mod:`repro.core.gossip_dist`) becomes ONE
collective-permute per round (see benchmarks/fused_step.py for the byte
accounting).

Layout: leaves are bucketed by dtype and concatenated in ``jax.tree.flatten``
order; each leaf is zero-padded to a multiple of ``LANE`` (=128) elements so
every leaf starts lane-aligned (the TPU vector lane width). The
:class:`FlatSpec` (offsets/shapes/dtypes) is fully static — built once per
trainer and reused across steps — and :meth:`FlatSpec.unflatten` produces
slice+reshape views that XLA fuses into consumers rather than materializing
copies.

``leading`` dims (the stacked replica axis of both engines) pass through
untouched: a ``[W, ...]``-stacked tree flattens to ``[W, total]`` buffers, so
per-replica scalars (gossip gates/coefficients) broadcast along axis 0.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

LANE = 128   # TPU vector lane width (elements); every leaf offset aligns to it


def _align(n: int, a: int = LANE) -> int:
    return ((n + a - 1) // a) * a


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """Static placement of one leaf inside its dtype bucket."""
    bucket: str                # dtype bucket key (canonical dtype name)
    offset: int                # element offset within the bucket (lane-aligned)
    size: int                  # elements per item (leading dims excluded)
    shape: Tuple[int, ...]     # per-item shape (leading dims excluded)
    dtype: Any                 # storage dtype the leaf unflattens to


@dataclasses.dataclass(frozen=True)
class FlatSpec:
    """Static layout of a pytree on the flat plane (cache one per trainer)."""
    treedef: Any
    leading: int                    # number of leading (replica) dims passed through
    lead_shape: Tuple[int, ...]
    slots: Tuple[LeafSlot, ...]     # one per leaf, flatten order
    totals: Dict[str, int]          # bucket -> padded total elements
    align: int = LANE               # per-leaf padding granularity (elements)

    @staticmethod
    def build(tree: PyTree, leading: int = 0, align: int = LANE) -> "FlatSpec":
        """Layout for ``tree`` (arrays or ShapeDtypeStructs); the first
        ``leading`` dims of every leaf are shared pass-through (replica) dims."""
        leaves, treedef = jax.tree.flatten(tree)
        assert leaves, "cannot build a FlatSpec over an empty tree"
        lead_shape = tuple(int(d) for d in leaves[0].shape[:leading])
        offsets: Dict[str, int] = {}
        slots: List[LeafSlot] = []
        for x in leaves:
            assert tuple(int(d) for d in x.shape[:leading]) == lead_shape, (
                "all leaves must share the leading dims", x.shape, lead_shape)
            shape = tuple(int(d) for d in x.shape[leading:])
            size = int(np.prod(shape)) if shape else 1
            bucket = jnp.dtype(x.dtype).name
            off = offsets.setdefault(bucket, 0)
            slots.append(LeafSlot(bucket, off, size, shape, jnp.dtype(x.dtype)))
            offsets[bucket] = off + _align(size, align)
        return FlatSpec(treedef, leading, lead_shape, tuple(slots), dict(offsets), align)

    # FlatSpec rides as STATIC pytree metadata (the aux_data of
    # repro.api.state.FlatState), so it must be hashable; the auto-generated
    # frozen-dataclass hash would choke on the ``totals`` dict.
    def __hash__(self):
        return hash((self.treedef, self.leading, self.lead_shape, self.slots,
                     tuple(sorted(self.totals.items())), self.align))

    def with_lead(self, lead_shape: Tuple[int, ...]) -> "FlatSpec":
        """The same layout bound to different leading (replica) dims — slots
        and totals are per-item, so only the pass-through dims change. Used at
        the boundaries: ``with_lead(())`` unflattens one replica row or an
        EASGD center, ``with_lead((W,))`` a whole stacked plane."""
        return dataclasses.replace(self, leading=len(lead_shape),
                                   lead_shape=tuple(int(d) for d in lead_shape))

    # ------------------------------------------------------------------ sizes
    @property
    def buckets(self) -> Tuple[str, ...]:
        return tuple(self.totals)

    def num_elements(self, bucket: Optional[str] = None) -> int:
        if bucket is not None:
            return self.totals[bucket]
        return sum(self.totals.values())

    # ------------------------------------------------------------------- ops
    def flatten(self, tree: PyTree) -> Dict[str, jax.Array]:
        """Tree -> one ``[*lead, total]`` buffer per dtype bucket.

        Bucketing follows the SPEC, not the argument's dtypes, so a float32
        gradient tree flattens into the layout of its bfloat16 parameter spec
        bucket-for-bucket (the buffers then carry the argument's dtype)."""
        leaves = jax.tree.flatten(tree)[0]
        assert len(leaves) == len(self.slots), (len(leaves), len(self.slots))
        parts: Dict[str, List[jax.Array]] = {}
        for x, s in zip(leaves, self.slots):
            flat = jnp.reshape(x, self.lead_shape + (s.size,))
            padded = _align(s.size, self.align)
            if padded != s.size:
                flat = jnp.pad(flat, [(0, 0)] * self.leading + [(0, padded - s.size)])
            parts.setdefault(s.bucket, []).append(flat)
        return {k: (v[0] if len(v) == 1 else jnp.concatenate(v, axis=-1))
                for k, v in parts.items()}

    def unflatten(self, bufs: Dict[str, jax.Array],
                  like: Optional[PyTree] = None) -> PyTree:
        """Buffers -> tree of slice/reshape views. ``like`` (optional)
        supplies per-leaf dtypes to cast to instead of the spec's storage
        dtypes (e.g. a velocity tree restored from promoted buffers)."""
        if like is not None:
            dts = [jnp.dtype(x.dtype) for x in jax.tree.flatten(like)[0]]
        else:
            dts = [s.dtype for s in self.slots]
        leaves = []
        for s, dt in zip(self.slots, dts):
            b = bufs[s.bucket]
            v = jax.lax.slice_in_dim(b, s.offset, s.offset + s.size, axis=-1)
            leaves.append(jnp.reshape(v, self.lead_shape + s.shape).astype(dt))
        return jax.tree.unflatten(self.treedef, leaves)

    def views(self, bufs: Dict[str, jax.Array]) -> PyTree:
        """:meth:`unflatten` with a SCATTER-based VJP — the flat-resident
        engines' loss boundary. Differentiating a loss through plain slice
        views gives each leaf a ``pad``-to-full-plane cotangent that XLA
        materializes separately (temp memory ∝ leaves x plane); this variant
        lands every leaf's cotangent in ONE zeros buffer per dtype bucket via
        in-place ``dynamic_update_slice`` (slots are disjoint), so gradients
        arrive already flat at plane-sized memory, with no concatenate and no
        per-leaf pads — step memory stays independent of tree depth.

        The slices and, in the backward pass, the scatter run under the
        ``flat_views`` scope, so a device trace names their time."""
        with jax.named_scope("flat_views"):
            return _views(self, bufs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _views(spec: FlatSpec, bufs: Dict[str, jax.Array]) -> PyTree:
    return spec.unflatten(bufs)


def _views_fwd(spec, bufs):
    return _views(spec, bufs), None


def _views_bwd(spec, _res, ct):
    leaves = jax.tree.flatten(ct)[0]
    out = {k: jnp.zeros(spec.lead_shape + (n,), jnp.dtype(k))
           for k, n in spec.totals.items()}
    for g, s in zip(leaves, spec.slots):
        if s.size == 0:
            continue
        flat = jnp.reshape(g, spec.lead_shape + (s.size,)).astype(jnp.dtype(s.bucket))
        out[s.bucket] = jax.lax.dynamic_update_slice_in_dim(
            out[s.bucket], flat, s.offset, axis=len(spec.lead_shape))
    return (out,)


_views.defvjp(_views_fwd, _views_bwd)
