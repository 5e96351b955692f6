"""Pallas TPU kernels: blockwise online-softmax (flash) attention.

Targets the MXU: (block_q x block_k) score tiles with f32 accumulators in
VMEM scratch, persisted across the innermost (kv) grid dimension — the
canonical TPU flash schedule (grid is executed sequentially on a core, so
scratch carries m/l/acc between kv steps).

Two entry points share the forward kernel:

* :func:`flash_attention` — the general forward in [B, H, S, hd] layout, with
  the variants the assigned archs need: causal masking with a query offset
  (decode), sliding window (gemma2 local / sw-decode), logit softcap (gemma2),
  GQA head grouping, and a dynamic kv_len (ring-buffer decode).
* :func:`causal_attention` — causal GQA self-attention for training, in the
  model's [B, S, H, hd] layout, with its own backward: the forward also
  emits the per-row log-sum-exp, and the dq and dk/dv kernels recompute each
  score tile from q, k and it. :func:`fits` says when it applies.

Each grid step takes a group of the G query heads of one kv head (q as [B,
Hkv, G, S, hd], a head-major layout XLA's producers and consumers absorb), so
the heads share the kv tiles and the mask, and dk/dv sum over them inside the
kernel. A group holds as many heads as fit the q block the kernels were tuned
at (:func:`_group_block`); the groups of a kv head are a grid axis of their own.

Every kernel skips the kv (or q) blocks that the causal mask hides entirely:
the compute sits under ``pl.when`` and the index map clamps to the last block
needed, so a skipped step fetches nothing new. Only blocks that cross the
diagonal build a mask.

Precision: scores, probabilities, the running max/denominator and every
accumulator are f32. The products' operands are rounded to bf16 on the MXU,
as XLA's f32 dots are at the TPU's default precision — except under a
``highest`` (or ``float32``) default-matmul-precision context and in
interpret mode, where they stay f32.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
NT = ((1,), (1,))        # contract the last dims: a @ b.T
NN = ((1,), (0,))        # a @ b
LANES = 128
TRAIN_BLOCK_Q = 1024     # training blocks, tuned on a v5e at hd=128 (PERF.md)
TRAIN_BLOCK_K = 1024
TRAIN_VMEM = 64 * 2**20  # scoped VMEM for the training kernels (a v5e core has 128 MiB)
# the largest [heads, block_q, hd] block a grid step takes: the 2 MiB of f32 the
# kernels were tuned and compiled at (4 heads x 1024 x 128)
MAX_Q_BLOCK = 4 * TRAIN_BLOCK_Q * LANES


def _mxu_dtype(interpret: bool):
    """Operand dtype of the kernels' products (module docstring)."""
    prec = jax.config.jax_default_matmul_precision
    if interpret or (prec is not None and str(prec).lower() in ("highest", "float32")):
        return jnp.float32
    return jnp.bfloat16


def _mm(a, b, dims, mxu):
    prec = jax.lax.Precision.HIGHEST if mxu == jnp.float32 else None
    return jax.lax.dot_general(a.astype(mxu), b.astype(mxu), (dims, ((), ())),
                               precision=prec, preferred_element_type=jnp.float32)


def _row(col, n):
    """(n, 1) -> (1, n) through an aligned (n, 128) transpose."""
    return jnp.broadcast_to(col, (n, LANES)).T[:1]


def _col(row, n):
    """(1, n) -> (n, 1) through an aligned (128, n) transpose."""
    return jnp.broadcast_to(row, (LANES, n)).T[:, :1]


def _causal_steps(causal, q_first, q_last, k_first, k_last, mask_all, step):
    """Run ``step(masked)`` on a (q block, kv block) pair the causal mask does
    not hide entirely; only a block crossing the diagonal builds a mask."""
    if not causal:
        step(mask_all)
        return
    run = k_first <= q_last
    if mask_all:
        pl.when(run)(lambda: step(True))
        return
    diag = k_last > q_first
    pl.when(run & diag)(lambda: step(True))
    pl.when(run & jnp.logical_not(diag))(lambda: step(False))


# --------------------------------------------------------------------- forward
def _mask(q_first, k_first, shape, kvlen, causal, window, q_rows: bool = True):
    """Valid entries of a score tile whose rows are queries (``q_rows``) or
    keys; ``kvlen`` None skips the valid-length test."""
    q_ax, k_ax = (0, 1) if q_rows else (1, 0)
    q_pos = q_first + jax.lax.broadcasted_iota(jnp.int32, shape, q_ax)
    kv_pos = k_first + jax.lax.broadcasted_iota(jnp.int32, shape, k_ax)
    mask = None if kvlen is None else kv_pos < kvlen
    if causal:
        mask = kv_pos <= q_pos if mask is None else mask & (kv_pos <= q_pos)
    if window:
        mask &= (q_pos - kv_pos) < window
    return mask


def _fwd_kernel(q_ref, k_ref, v_ref, kvlen_ref, o_ref, *rest, scale: float,
                causal: bool, window: int, softcap: float, q_offset: int,
                block_q: int, block_k: int, num_kv_blocks: int, mask_all: bool, mxu):
    """A group of one kv head's query heads (``q_ref`` [gb, bq, hd]) against
    one kv block: the heads share the kv tiles and the mask."""
    lse_ref = rest[0] if len(rest) == 4 else None
    m_scr, l_scr, acc_scr = rest[-3:]
    qi = pl.program_id(3)
    ki = pl.program_id(4)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_first = q_offset + qi * block_q
    k_first = ki * block_k

    def step(masked: bool):
        k, v = k_ref[...].astype(mxu), v_ref[...].astype(mxu)
        mask = (_mask(q_first, k_first, (block_q, block_k),
                      kvlen_ref[0] if mask_all else None, causal, window)
                if masked else None)
        for g in range(q_ref.shape[0]):
            s = _mm(q_ref[g], k, NT, mxu) * scale                      # [bq, bk]
            if softcap:
                s = softcap * jnp.tanh(s / softcap)
            if mask is not None:
                s = jnp.where(mask, s, NEG_INF)
            m_prev = m_scr[g]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[g] = l_scr[g] * corr + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[g] = acc_scr[g] * corr + _mm(p, v, NN, mxu)
            m_scr[g] = m_new

    _causal_steps(causal, q_first, q_first + block_q - 1, k_first,
                  k_first + block_k - 1, mask_all, step)

    @pl.when(ki == num_kv_blocks - 1)
    def _fin():
        for g in range(q_ref.shape[0]):
            l = l_scr[g]
            o_ref[g] = (acc_scr[g] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
            if lse_ref is not None:
                lse_ref[g] = _row(m_scr[g] + jnp.log(l), block_q)


def _last_kv(i, *, causal, q_offset, block_q, block_k, nk):
    """The last kv block that q block ``i`` reads (all of them unless causal)."""
    if not causal:
        return nk - 1
    return jnp.minimum((q_offset + (i + 1) * block_q - 1) // block_k, nk - 1)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "q_offset", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, kv_len=None, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, q_offset: int = 0,
                    block_q: int = 128, block_k: int = 512, interpret: bool = False):
    """q: [B, H, Sq, hd]; k, v: [B, Hkv, Skv, hd]. Returns [B, H, Sq, hd].

    kv_len: optional scalar int32 — number of valid kv rows (ring decode).
    Sq/Skv are padded to block multiples internally.
    """
    B, H, Sq, hd = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    block_q = min(block_q, max(8, Sq))
    block_k = min(block_k, Skv)
    pq = (block_q - Sq % block_q) % block_q
    pk = (block_k - Skv % block_k) % block_k
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    kvl = jnp.asarray(Skv if kv_len is None else kv_len, jnp.int32).reshape(1)
    out = _forward(q.reshape(B, Hkv, H // Hkv, Sq + pq, hd), k, v, kvl, causal=causal,
                   window=window, softcap=softcap, q_offset=q_offset, block_q=block_q,
                   block_k=block_k, mask_all=True, with_lse=False,
                   mxu=_mxu_dtype(interpret), interpret=interpret)
    return out.reshape(B, H, Sq + pq, hd)[:, :, :Sq]


def _forward(q, k, v, kvl, *, causal, window, softcap, q_offset, block_q, block_k,
             mask_all, with_lse, mxu, interpret, name="flash_attention", vmem=None):
    """The forward kernel over q [B, Hkv, G, Sq, hd] x k, v [B, Hkv, Skv, hd]
    (block multiples): o like q, and with ``with_lse`` the log-sum-exp rows
    [B, Hkv, G, 1, Sq]."""
    B, Hkv, G, Sq, hd = q.shape
    nq, nk = Sq // block_q, k.shape[2] // block_k
    group = _group_block(G, block_q, hd)
    if group is None:
        raise ValueError(f"one head's {block_q} x {hd} q block exceeds {MAX_Q_BLOCK} elements")
    last = functools.partial(_last_kv, causal=causal, q_offset=q_offset,
                             block_q=block_q, block_k=block_k, nk=nk)

    def kv_map(b, h, g, i, j):
        return b, h, jnp.minimum(j, last(i)), 0

    q_spec = pl.BlockSpec((None, None, group, block_q, hd),
                          lambda b, h, g, i, j: (b, h, g, i, 0))
    out_specs, out_shape = [q_spec], [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((None, None, group, 1, block_q),
                                      lambda b, h, g, i, j: (b, h, g, 0, i)))
        out_shape.append(jax.ShapeDtypeStruct((B, Hkv, G, 1, Sq), jnp.float32))
    out = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=hd ** -0.5, causal=causal, window=window,
            softcap=softcap, q_offset=q_offset, block_q=block_q, block_k=block_k,
            num_kv_blocks=nk, mask_all=mask_all, mxu=mxu),
        grid=(B, Hkv, G // group, nq, nk),
        in_specs=[
            q_spec,
            pl.BlockSpec((None, None, block_k, hd), kv_map),
            pl.BlockSpec((None, None, block_k, hd), kv_map),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((group, block_q, 1), jnp.float32),     # running max m
            pltpu.VMEM((group, block_q, 1), jnp.float32),     # running denom l
            pltpu.VMEM((group, block_q, hd), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
        name=name,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
    )(q, k, v, kvl)
    return out if with_lse else out[0]


# ------------------------------------------------------ causal training path
def fits(*, backend: str, devices: int, seq_q: int, seq_kv: int, groups: int,
         head_dim: int, window, softcap: float) -> bool:
    """Whether :func:`causal_attention` takes a causal self-attention call:
    in a TPU program that runs on one device, a square (Sq == Skv) score
    matrix the training blocks tile, a head dim that is a multiple of 128
    whose one-head q block fits MAX_Q_BLOCK (of the ``groups`` query heads
    per kv head a step takes as many as fit), no softcap, and a window that
    is statically 0. Everything else (traced or non-zero windows, softcaps, odd
    shapes, other backends) stays on the jnp path; so does a program that may
    be partitioned over several devices: a pallas_call has no GSPMD sharding
    rule, and JAX refuses to lower one into a program it partitions itself."""
    return (backend == "tpu" and devices == 1 and seq_q == seq_kv
            and head_dim % LANES == 0
            and _train_blocks(seq_q, groups, head_dim) is not None
            and isinstance(window, int) and window == 0 and not softcap)


def _group_block(G: int, block_q: int, hd: int):
    """The most of a kv head's G query heads one grid step takes: the largest
    divisor of G whose [heads, block_q, hd] block is at most MAX_Q_BLOCK
    elements, or None if one head's block is larger."""
    fit = [g for g in range(1, G + 1) if G % g == 0 and g * block_q * hd <= MAX_Q_BLOCK]
    return max(fit) if fit else None


def _train_blocks(S: int, G: int, hd: int):
    """(group, block_q, block_k) for sequence length S and G query heads per
    kv head, or None if the blocks cannot tile S or one head's block is too
    large."""
    bq, bk = min(TRAIN_BLOCK_Q, S), min(TRAIN_BLOCK_K, S)
    if S % bq or S % bk or bq % LANES or bk % LANES:
        return None
    group = _group_block(G, bq, hd)
    return None if group is None else (group, bq, bk)


class _Opts(NamedTuple):
    block_q: int
    block_k: int
    interpret: bool
    mxu: object


def causal_attention(q, k, v, *, block_q: int = 0, block_k: int = 0,
                     interpret: bool = False):
    """Causal GQA self-attention, differentiable by its own kernels.

    q: [B, S, H, hd]; k, v: [B, S, Hkv, hd] (the model's layout). Returns
    [B, S, H, hd] in q's dtype. Blocks default to the training blocks
    (:func:`fits`); hd must be a multiple of 128 and S of both blocks.
    """
    B, S, H, hd = q.shape
    Hkv = k.shape[2]
    _, dq, dk = _train_blocks(S, H // Hkv, hd) or (0, 0, 0)
    opts = _Opts(block_q or dq, block_k or dk, interpret, _mxu_dtype(interpret))
    if not opts.block_q or S % opts.block_q or not opts.block_k or S % opts.block_k:
        raise ValueError(f"blocks {opts.block_q} x {opts.block_k} do not tile S={S}")
    t = functools.partial(jnp.swapaxes, axis1=1, axis2=2)
    q5 = t(q).reshape(B, Hkv, H // Hkv, S, hd)
    return t(_causal(q5, t(k), t(v), opts).reshape(B, H, S, hd))


def _causal_forward(q, k, v, opts: _Opts):
    return _forward(q, k, v, jnp.full((1,), q.shape[3], jnp.int32), causal=True,
                    window=0, softcap=0.0, q_offset=0, block_q=opts.block_q,
                    block_k=opts.block_k, mask_all=False, with_lse=True,
                    mxu=opts.mxu, interpret=opts.interpret, name="flash_fwd_lse",
                    vmem=TRAIN_VMEM)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _causal(q, k, v, opts: _Opts):
    with jax.named_scope("flash_fwd"):
        return _causal_forward(q, k, v, opts)[0]


def _causal_fwd(q, k, v, opts: _Opts):
    with jax.named_scope("flash_fwd"):
        o, lse = _causal_forward(q, k, v, opts)
    return o, (q, k, v, o, lse)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
               lse_scr, di_scr, acc_scr, *, scale, block_q, block_k, nk, mxu):
    qi = pl.program_id(3)
    ki = pl.program_id(4)
    groups = q_ref.shape[0]

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        for g in range(groups):
            lse_scr[g] = _col(lse_ref[g], block_q)
            di_scr[g] = _col(di_ref[g], block_q)

    q_first, k_first = qi * block_q, ki * block_k

    def step(masked: bool):
        k, v = k_ref[...].astype(mxu), v_ref[...].astype(mxu)
        mask = (_mask(q_first, k_first, (block_q, block_k), None, True, 0)
                if masked else None)
        for g in range(groups):
            s = _mm(q_ref[g], k, NT, mxu) * scale                      # [bq, bk]
            if mask is not None:
                s = jnp.where(mask, s, NEG_INF)
            p = jnp.exp(s - lse_scr[g])
            ds = p * (_mm(do_ref[g], v, NT, mxu) - di_scr[g])
            acc_scr[g] += _mm(ds, k, NN, mxu)

    _causal_steps(True, q_first, q_first + block_q - 1, k_first,
                  k_first + block_k - 1, False, step)

    @pl.when(ki == nk - 1)
    def _fin():
        dq_ref[...] = (acc_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, scale, block_q, block_k, ng, nq, mxu):
    """One kv block against one q block of a group of its query heads, the
    scores transposed ([bk, bq]) so the log-sum-exp and di rows broadcast as
    read; dk and dv sum over the heads, the groups and the q blocks in f32
    scratch."""
    kj = pl.program_id(2)
    gi = pl.program_id(3)
    qi = pl.program_id(4)

    @pl.when((gi == 0) & (qi == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q_first, k_first = qi * block_q, kj * block_k

    def step(masked: bool):
        k, v = k_ref[...].astype(mxu), v_ref[...].astype(mxu)
        mask = (_mask(q_first, k_first, (block_k, block_q), None, True, 0, q_rows=False)
                if masked else None)
        dk, dv = dk_scr[...], dv_scr[...]
        for g in range(q_ref.shape[0]):
            q, do = q_ref[g], do_ref[g]
            st = _mm(k, q, NT, mxu) * scale                           # [bk, bq]
            if mask is not None:
                st = jnp.where(mask, st, NEG_INF)
            pt = jnp.exp(st - lse_ref[g])
            dv += _mm(pt, do, NN, mxu)
            dst = pt * (_mm(v, do, NT, mxu) - di_ref[g])
            dk += _mm(dst, q, NN, mxu)
        dk_scr[...], dv_scr[...] = dk, dv

    _causal_steps(True, q_first, q_first + block_q - 1, k_first,
                  k_first + block_k - 1, False, step)

    @pl.when((gi == ng - 1) & (qi == nq - 1))
    def _fin():
        dk_ref[...] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _causal_bwd(opts: _Opts, res, do):
    q, k, v, o, lse = res
    B, Hkv, G, S, hd = q.shape
    bq, bk = opts.block_q, opts.block_k
    gb = _group_block(G, bq, hd)
    ng, nq, nk = G // gb, S // bq, S // bk
    scale = hd ** -0.5
    with jax.named_scope("flash_bwd"):
        di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                     axis=-1)[..., None, :]                          # [B, Hkv, G, 1, S]
        last = functools.partial(_last_kv, causal=True, q_offset=0, block_q=bq,
                                 block_k=bk, nk=nk)

        def kv_map(b, h, g, i, j):
            return b, h, jnp.minimum(j, last(i)), 0

        q_spec = pl.BlockSpec((None, None, gb, bq, hd), lambda b, h, g, i, j: (b, h, g, i, 0))
        row_spec = pl.BlockSpec((None, None, gb, 1, bq), lambda b, h, g, i, j: (b, h, g, 0, i))
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, scale=scale, block_q=bq, block_k=bk,
                              nk=nk, mxu=opts.mxu),
            grid=(B, Hkv, ng, nq, nk),
            in_specs=[q_spec, pl.BlockSpec((None, None, bk, hd), kv_map),
                      pl.BlockSpec((None, None, bk, hd), kv_map), q_spec, row_spec, row_spec],
            out_specs=q_spec,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            scratch_shapes=[pltpu.VMEM((gb, bq, 1), jnp.float32),
                            pltpu.VMEM((gb, bq, 1), jnp.float32),
                            pltpu.VMEM((gb, bq, hd), jnp.float32)],
            interpret=opts.interpret,
            name="flash_dq",
            compiler_params=pltpu.CompilerParams(vmem_limit_bytes=TRAIN_VMEM),
        )(q, k, v, do, lse, di)

        def first_q(j):
            # the first q block that kv block j is visible to
            return (j * bk) // bq

        kv_spec = pl.BlockSpec((None, None, bk, hd), lambda b, h, j, g, i: (b, h, j, 0))
        q2 = pl.BlockSpec((None, None, gb, bq, hd),
                          lambda b, h, j, g, i: (b, h, g, jnp.maximum(i, first_q(j)), 0))
        row2 = pl.BlockSpec((None, None, gb, 1, bq),
                            lambda b, h, j, g, i: (b, h, g, 0, jnp.maximum(i, first_q(j))))
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel, scale=scale, block_q=bq, block_k=bk,
                              ng=ng, nq=nq, mxu=opts.mxu),
            grid=(B, Hkv, nk, ng, nq),
            in_specs=[q2, kv_spec, kv_spec, q2, row2, row2],
            out_specs=[kv_spec, kv_spec],
            out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                       jax.ShapeDtypeStruct(v.shape, v.dtype)],
            scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                            pltpu.VMEM((bk, hd), jnp.float32)],
            interpret=opts.interpret,
            name="flash_dkv",
            compiler_params=pltpu.CompilerParams(vmem_limit_bytes=TRAIN_VMEM),
        )(q, k, v, do, lse, di)
    return dq, dk, dv


_causal.defvjp(_causal_fwd, _causal_bwd)
