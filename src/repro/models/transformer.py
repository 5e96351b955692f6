"""Transformer LM: segment-planned, scan-over-layers, with train / prefill /
decode entry points.

A model is a list of *events*:
  ("seg", name)     scan over a stacked homogeneous segment of blocks
  ("cross", i)      one standalone cross-attention block (Llama-3.2-V)
  ("shared", site)  one application of a shared block (Zamba2)

Per-layer static variation inside a segment (gemma2 local/global windows,
anything flag-like) rides along the scan as xs arrays, so the HLO stays one
While loop per segment regardless of depth.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig
from repro.models import blocks
from repro.models.common import dense_init, init_rmsnorm, rmsnorm, softcap, split_tree
from repro.launch.sharding import is_axes_leaf

PyTree = Any


@dataclasses.dataclass(frozen=True)
class Segment:
    name: str
    kind: str                 # blocks.py kind
    count: int
    use_moe: bool = False
    windows: Optional[Tuple[int, ...]] = None   # per-layer window (gemma2)


@dataclasses.dataclass(frozen=True)
class Plan:
    events: Tuple[Tuple[str, Any], ...]
    segments: Tuple[Segment, ...]
    num_cross: int = 0
    num_shared_blocks: int = 0
    num_shared_sites: int = 0


def make_plan(cfg: ModelConfig) -> Plan:
    events: List[Tuple[str, Any]] = []
    segments: List[Segment] = []

    def add_seg(kind, count, use_moe=False, windows=None):
        name = f"seg{len(segments)}_{kind}" + ("_moe" if use_moe else "")
        segments.append(Segment(name, kind, count, use_moe, windows))
        events.append(("seg", name))

    if cfg.arch_type in ("dense", "audio", "vlm", "moe"):
        kind = "attn_cross" if cfg.arch_type == "audio" else "attn"
        xlayers = set(cfg.vlm.cross_attn_layers) if (cfg.vlm is not None) else set()
        moe_first_dense = cfg.moe.first_dense_layers if cfg.moe is not None else 0
        # split layers into runs between cross-attn insertions / moe boundary
        cuts = sorted({moe_first_dense} | {i + 1 for i in xlayers} | {cfg.num_layers})
        cuts = [c for c in cuts if 0 < c <= cfg.num_layers]
        start, n_cross = 0, 0
        for c in cuts:
            count = c - start
            if count > 0:
                use_moe = cfg.moe is not None and start >= moe_first_dense
                windows = None
                if cfg.local_window:
                    # gemma2: even layers local, odd layers global
                    windows = tuple(cfg.local_window if (start + j) % 2 == 0 else 0
                                    for j in range(count))
                add_seg(kind, count, use_moe, windows)
            if (c - 1) in xlayers:
                events.append(("cross", n_cross))
                n_cross += 1
            start = c
        return Plan(tuple(events), tuple(segments), num_cross=n_cross)

    if cfg.arch_type == "ssm" and cfg.xlstm is not None:
        x = cfg.xlstm
        pattern = ["slstm" if (i % x.slstm_every == x.slstm_offset) else "mlstm"
                   for i in range(cfg.num_layers)]
        i = 0
        while i < cfg.num_layers:
            j = i
            while j < cfg.num_layers and pattern[j] == pattern[i]:
                j += 1
            add_seg(pattern[i], j - i)
            i = j
        return Plan(tuple(events), tuple(segments))

    if cfg.arch_type in ("ssm", "hybrid"):
        if cfg.arch_type == "ssm":
            add_seg("mamba", cfg.num_layers)
            return Plan(tuple(events), tuple(segments))
        h = cfg.hybrid
        n_sites = 0
        start = 0
        while start < cfg.num_layers:
            count = min(h.shared_attn_every, cfg.num_layers - start)
            add_seg("mamba", count)
            start += count
            if start < cfg.num_layers:
                events.append(("shared", n_sites))
                n_sites += 1
        return Plan(tuple(events), tuple(segments),
                    num_shared_blocks=h.num_shared_blocks, num_shared_sites=n_sites)

    raise ValueError(cfg.arch_type)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_lm(key, cfg: ModelConfig, dtype=jnp.float32) -> Tuple[PyTree, PyTree]:
    plan = make_plan(cfg)
    keys = jax.random.split(key, 8 + len(plan.segments))
    params: dict = {}
    axes: dict = {}

    # embeddings
    n_embed = cfg.audio.num_codebooks if cfg.audio is not None else 1
    p, a = dense_init(keys[0], (n_embed, cfg.vocab_size, cfg.d_model),
                      (None, "vocab", "embed"), dtype, fan_in=cfg.d_model, scale=0.5)
    params["embed"], axes["embed"] = p, a

    segs_p, segs_a = {}, {}
    for i, seg in enumerate(plan.segments):
        def one(k, seg=seg):
            return blocks.init_block(k, seg.kind, cfg, use_moe=seg.use_moe, dtype=dtype)
        sp_list = [one(k) for k in jax.random.split(keys[1 + i], seg.count)]
        sp = jax.tree.map(lambda *xs: jnp.stack(xs), *[p_ for p_, _ in sp_list])
        sa = jax.tree.map(lambda a_: (None,) + tuple(a_), sp_list[0][1], is_leaf=is_axes_leaf)
        segs_p[seg.name], segs_a[seg.name] = sp, sa
    params["segments"], axes["segments"] = segs_p, segs_a

    kidx = 1 + len(plan.segments)
    if plan.num_cross:
        cb = [blocks.init_block(k, "cross_blk", cfg, dtype=dtype)
              for k in jax.random.split(keys[kidx], plan.num_cross)]
        params["cross"] = jax.tree.map(lambda *xs: jnp.stack(xs), *[p_ for p_, _ in cb])
        axes["cross"] = jax.tree.map(lambda a_: (None,) + tuple(a_), cb[0][1], is_leaf=is_axes_leaf)
    if plan.num_shared_blocks:
        sb = [blocks.init_block(k, "attn", cfg, dtype=dtype)
              for k in jax.random.split(keys[kidx + 1], plan.num_shared_blocks)]
        params["shared"] = jax.tree.map(lambda *xs: jnp.stack(xs), *[p_ for p_, _ in sb])
        axes["shared"] = jax.tree.map(lambda a_: (None,) + tuple(a_), sb[0][1], is_leaf=is_axes_leaf)

    p, a = init_rmsnorm(cfg.d_model, dtype)
    params["final_norm"], axes["final_norm"] = p, a
    if not cfg.tie_embeddings:
        n_heads_out = cfg.audio.num_codebooks if cfg.audio is not None else 1
        p, a = dense_init(keys[kidx + 2], (n_heads_out, cfg.d_model, cfg.vocab_size),
                          (None, "embed", "vocab"), dtype, fan_in=cfg.d_model)
        params["lm_head"], axes["lm_head"] = p, a
    return params, axes


def abstract_lm(cfg: ModelConfig, dtype=jnp.float32):
    """(ShapeDtypeStruct params, axes) without allocating anything — the axes
    tree is static Python, captured as a tracing side effect."""
    box = {}

    def f(k):
        p, a = init_lm(k, cfg, dtype)
        box["axes"] = a
        return p

    sds = jax.eval_shape(f, jax.random.PRNGKey(0))
    return sds, box["axes"]


def abstract_cache(cfg: ModelConfig, batch: int, max_len: int, *, dtype=jnp.float32,
                   window: int = 0):
    box = {}

    def f():
        c, a = init_cache(cfg, batch, max_len, dtype=dtype, window=window)
        box["axes"] = a
        return c

    sds = jax.eval_shape(f)
    return sds, box["axes"]


# ---------------------------------------------------------------------------
# embedding / head helpers
# ---------------------------------------------------------------------------

@jax.named_scope("embed")
def embed_tokens(params, cfg: ModelConfig, tokens):
    """tokens: [B, S] (or [B, K, S] for audio codebooks) -> [B, S, d]."""
    emb = params["embed"]
    if cfg.audio is not None:
        # sum codebook embeddings (MusicGen token interleave collapsed)
        xs = [emb[k][tokens[:, k]] for k in range(cfg.audio.num_codebooks)]
        return sum(xs)
    return emb[0][tokens]


def lm_logits(params, cfg: ModelConfig, x):
    """x: [B, S, d] -> [B, S, V] (or [B, K, S, V] for audio)."""
    if cfg.tie_embeddings:
        heads = jnp.swapaxes(params["embed"], 1, 2)     # [K, d, V]
    else:
        heads = params["lm_head"]
    logits = jnp.einsum("bsd,kdv->bksv", x, heads.astype(x.dtype))
    if cfg.final_logit_softcap:
        logits = softcap(logits.astype(jnp.float32), cfg.final_logit_softcap).astype(logits.dtype)
    if cfg.audio is None:
        return logits[:, 0]
    return logits


# ---------------------------------------------------------------------------
# forward (training)
# ---------------------------------------------------------------------------

def _scan_segment(seg: Segment, seg_params, x, cfg: ModelConfig, cond):
    # per-layer windows ride the scan; without them every layer's window is
    # the static 0, which attention's kernel dispatch can see
    windows = None if seg.windows is None else jnp.array(seg.windows, jnp.int32)

    def body(carry, layer):
        xc, aux = carry
        p, w = layer
        y, a = blocks.block_forward(seg.kind, p, xc, cfg, use_moe=seg.use_moe,
                                    window=0 if w is None else w, cond=cond)
        return (y, aux + a), None

    if cfg.remat:
        body = jax.checkpoint(body)
    # the scan's own work (per-layer weight slices, norms, residual adds) is
    # named; attention and ffn inside carry their own, innermost, scopes
    with jax.named_scope("layer_scan"):
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   (seg_params, windows))
    return x, aux


def forward(params, cfg: ModelConfig, tokens, cond=None):
    """Training forward. tokens: [B, S] (audio: [B, K, S]).
    cond: stubbed modality embeddings [B, T, e] for vlm/audio.
    Returns (hidden [B, S, d], aux_loss)."""
    plan = make_plan(cfg)
    x = embed_tokens(params, cfg, tokens)
    aux_total = jnp.zeros((), jnp.float32)
    shared_site = 0

    def one_block(kind, p, x, cond):
        # standalone (non-scanned) blocks need their own remat: without it the
        # backward keeps each one's attention internals live (§Perf iter. 2)
        return blocks.block_forward(kind, p, x, cfg, cond=cond)

    if cfg.remat:
        one_block = jax.checkpoint(one_block, static_argnums=(0,))

    for ev, arg in plan.events:
        if ev == "seg":
            seg = next(s for s in plan.segments if s.name == arg)
            x, aux = _scan_segment(seg, params["segments"][arg], x, cfg, cond)
            aux_total = aux_total + aux
        elif ev == "cross":
            p = jax.tree.map(lambda t: t[arg], params["cross"])
            x, _ = one_block("cross_blk", p, x, cond)
        elif ev == "shared":
            p = jax.tree.map(lambda t: t[arg % plan.num_shared_blocks], params["shared"])
            x, aux = one_block("attn", p, x, None)
            aux_total = aux_total + aux
            shared_site += 1
    return rmsnorm(params["final_norm"], x, cfg.norm_eps), aux_total


@jax.named_scope("head_loss")
def chunked_ce_loss(params, cfg: ModelConfig, hidden, labels, chunk: int = 256):
    """Cross-entropy without materializing [B, S, V]: scan over seq chunks.

    labels: [B, S] (audio: [B, K, S]). Positions with label < 0 are masked.
    """
    B, S, d = hidden.shape
    chunk = min(chunk, S)
    assert S % chunk == 0
    n = S // chunk
    if cfg.tie_embeddings:
        heads = jnp.swapaxes(params["embed"], 1, 2)
    else:
        heads = params["lm_head"]
    K = heads.shape[0]
    labels_k = labels if labels.ndim == 3 else labels[:, None]       # [B, K, S]

    hc = jnp.moveaxis(hidden.reshape(B, n, chunk, d), 1, 0)          # [n, B, c, d]
    lc = jnp.moveaxis(labels_k.reshape(B, K, n, chunk), 2, 0)        # [n, B, K, c]

    def body(carry, xs):
        tot, cnt = carry
        h, lab = xs
        logits = jnp.einsum("bcd,kdv->bkcv", h, heads.astype(h.dtype)).astype(jnp.float32)
        if cfg.final_logit_softcap:
            logits = softcap(logits, cfg.final_logit_softcap)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, jnp.maximum(lab, 0)[..., None], axis=-1)[..., 0]
        mask = (lab >= 0).astype(jnp.float32)
        tot = tot + jnp.sum((lse - gold) * mask)
        cnt = cnt + jnp.sum(mask)
        return (tot, cnt), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())), (hc, lc))
    return tot / jnp.maximum(cnt, 1.0)


def lm_loss(params, cfg: ModelConfig, tokens, labels, cond=None, aux_coef: float = 0.01):
    hidden, aux = forward(params, cfg, tokens, cond)
    ce = chunked_ce_loss(params, cfg, hidden, labels)
    return ce + aux_coef * aux, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# caches / prefill / decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int, *, dtype=jnp.float32,
               window: int = 0) -> Tuple[PyTree, PyTree]:
    plan = make_plan(cfg)
    cache, axes = {"segments": {}, "pos": jnp.zeros((), jnp.int32)}, {"segments": {}, "pos": ()}

    def stack_cache(kind, count):
        c, a = blocks.init_block_cache(kind, cfg, batch, max_len, dtype=dtype, window=window)
        cs = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (count,) + x.shape), c)
        as_ = jax.tree.map(lambda t: (None,) + tuple(t), a, is_leaf=is_axes_leaf)
        return cs, as_

    for seg in plan.segments:
        cache["segments"][seg.name], axes["segments"][seg.name] = stack_cache(seg.kind, seg.count)
    if plan.num_shared_sites:
        cache["shared_sites"], axes["shared_sites"] = stack_cache("attn", plan.num_shared_sites)
    return cache, axes


def _scan_segment_decode(seg: Segment, seg_params, seg_cache, x, pos, cfg, cond, window,
                         kv_start=None):
    windows = (jnp.array(seg.windows, jnp.int32) if seg.windows is not None
               else jnp.full((seg.count,), window, jnp.int32))

    def body(xc, layer):
        p, c, w = layer
        # `window` (python int) selects the ring-buffer mode; the traced
        # per-layer `w` masks local-attention layers in full-cache mode.
        y, c2 = blocks.block_decode(seg.kind, p, xc, c, pos, cfg, use_moe=seg.use_moe,
                                    window=window, window_mask=w, cond=cond,
                                    kv_start=kv_start)
        return y, c2

    x, new_cache = jax.lax.scan(body, x, (seg_params, seg_cache, windows))
    return x, new_cache


def decode_step(params, cfg: ModelConfig, cache, tokens, cond=None, *, window: int = 0,
                kv_start=None):
    """One-token decode. tokens: [B, 1] (audio: [B, K, 1]).
    kv_start (optional [B]): per-batch-row first valid cache position — the
    continuous-batching slot boundary (repro.serve): a request admitted into
    a recycled slot attends only to its own cache rows. None traces the
    original single-stream program unchanged.
    Returns (logits [B, V] or [B, K, V], new cache)."""
    plan = make_plan(cfg)
    pos = cache["pos"]
    x = embed_tokens(params, cfg, tokens)
    new_cache = {"segments": {}, "pos": pos + 1}
    shared_site = 0
    for ev, arg in plan.events:
        if ev == "seg":
            seg = next(s for s in plan.segments if s.name == arg)
            x, nc = _scan_segment_decode(seg, params["segments"][arg],
                                         cache["segments"][arg], x, pos, cfg, cond, window,
                                         kv_start=kv_start)
            new_cache["segments"][arg] = nc
        elif ev == "cross":
            p = jax.tree.map(lambda t: t[arg], params["cross"])
            x, _ = blocks.block_decode("cross_blk", p, x, {}, pos, cfg, cond=cond)
        elif ev == "shared":
            p = jax.tree.map(lambda t: t[arg % plan.num_shared_blocks], params["shared"])
            c = jax.tree.map(lambda t: t[shared_site], cache["shared_sites"])
            x, nc = blocks.block_decode("attn", p, x, c, pos, cfg, window=window,
                                        kv_start=kv_start)
            if "shared_sites" not in new_cache:
                new_cache["shared_sites"] = jax.tree.map(
                    lambda t: jnp.zeros_like(t), cache["shared_sites"])
            new_cache["shared_sites"] = jax.tree.map(
                lambda buf, v: buf.at[shared_site].set(v), new_cache["shared_sites"], nc)
            shared_site += 1
    if "shared_sites" in cache and "shared_sites" not in new_cache:
        new_cache["shared_sites"] = cache["shared_sites"]
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = lm_logits(params, cfg, x)
    return (logits[:, :, 0] if cfg.audio is not None else logits[:, 0]), new_cache


def prefill(params, cfg: ModelConfig, tokens, cond=None, cache_dtype=jnp.float32,
            max_len: int = 0):
    """Full-sequence prefill: returns (last-token logits, cache). Attention
    caches are padded to ``max_len`` rows so decode can continue in place."""
    plan = make_plan(cfg)
    x = embed_tokens(params, cfg, tokens)
    S = x.shape[1]
    cache = {"segments": {}, "pos": jnp.full((), S, jnp.int32)}
    shared_site = 0
    for ev, arg in plan.events:
        if ev == "seg":
            seg = next(s for s in plan.segments if s.name == arg)
            windows = (jnp.array(seg.windows, jnp.int32) if seg.windows is not None
                       else jnp.zeros((seg.count,), jnp.int32))

            def body(xc, layer, seg=seg):
                p, w = layer
                y, c = blocks.block_prefill(seg.kind, p, xc, cfg, use_moe=seg.use_moe,
                                            window=w, cond=cond, cache_dtype=cache_dtype,
                                            max_len=max_len)
                return y, c

            x, seg_cache = jax.lax.scan(body, x, (params["segments"][arg], windows))
            cache["segments"][arg] = seg_cache
        elif ev == "cross":
            p = jax.tree.map(lambda t: t[arg], params["cross"])
            x, _ = blocks.block_forward("cross_blk", p, x, cfg, cond=cond)
        elif ev == "shared":
            p = jax.tree.map(lambda t: t[arg % plan.num_shared_blocks], params["shared"])
            x, c = blocks.block_prefill("attn", p, x, cfg, cache_dtype=cache_dtype,
                                        max_len=max_len)
            if "shared_sites" not in cache:
                cache["shared_sites"] = jax.tree.map(
                    lambda v: jnp.zeros((plan.num_shared_sites,) + v.shape, v.dtype), c)
            cache["shared_sites"] = jax.tree.map(
                lambda buf, v: buf.at[shared_site].set(v), cache["shared_sites"], c)
            shared_site += 1
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    last = x[:, -1:]
    logits = lm_logits(params, cfg, last)
    return (logits[:, :, 0] if cfg.audio is not None else logits[:, 0]), cache
