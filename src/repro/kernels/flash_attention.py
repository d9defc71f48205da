"""Blocked online-softmax attention (flash attention) as Pallas TPU
kernels — forward AND backward.

TPU adaptation notes (vs the CUDA original): tiles live in VMEM sized for
the MXU (block dims multiples of 128 where the dtype allows); the running
(m, l, acc) statistics persist in VMEM scratch across the innermost
(sequential) KV-block grid dimension, while (batch, head, q-block) are
parallel grid dims. GQA is handled in the index map (q head h reads kv
head h // group). Causal and sliding-window masks are applied from
absolute positions, so the same kernels serve train, prefill and the
windowed long_500k path.

Backward structure (FlashAttention-2): the forward additionally emits the
per-row logsumexp ``lse = m + log(l)`` so the VJP saves ``(q, k, v, o,
lse)`` — O(S·D) residuals — instead of rematerializing the O(Sq·Skv)
score/softmax matrices. Inside the kernels the per-row statistics (m, l,
lse, delta) are [rows, 1] columns and cross HBM as [B, H, S, 1]: a
(1, 1, bq, 1) block obeys Mosaic's rule that a block's last two dims
are multiples of (8, 128) or equal to the array's, where a rank-3
(1, 1, bq) block over [B, H, S] does not. Three kernels then compute the
gradients, each recomputing ``p = exp(s - lse)`` one block at a time:

  * ``_bwd_preprocess_kernel``: ``delta = rowsum(dO * O)`` (the softmax
    Jacobian's diagonal correction), grid over q blocks.
  * ``_bwd_dkv_kernel``: grid over KV blocks (parallel) with a sequential
    inner dimension over (GQA query group x q block); dK/dV accumulate in
    float32 VMEM scratch and the query-group contributions sum into the
    shared KV head.
  * ``_bwd_dq_kernel``: grid over Q blocks (parallel) with a sequential
    inner dimension over KV blocks; dQ accumulates in VMEM scratch.

Uneven sequence lengths (e.g. vision token counts) are handled by padding
Sq/Skv up to a block multiple and masking the tail from absolute
positions (``kp < kv_len``); padded q rows carry zero cotangents, so they
contribute nothing to dK/dV and their dQ rows are sliced off.

The serving kernels read K/V through a block table over physical page
pools, stored page-major as layer stacks [L, NB, bs, Hkv * D] (a token's
row holds every KV head) and passed whole with the layer
scalar-prefetched, so no caller slices or relayouts a pool for them.
Both score every query head against a page's rows in one matmul from a
block-diagonal query. Chunked prefill walks the table as its sequential
grid dimension, like the KV blocks above, one page of all heads a step.
Decode instead gives each request one grid step and walks only its live
pages in a ``fori_loop``, copying them by hand into double-buffered
VMEM, so its cost follows the live context and not the table's width.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _block_and_pad(block: int, s: int) -> tuple:
    """Tile size and tail padding for a sequence length that need not be a
    multiple of the requested block (pad + mask instead of asserting)."""
    b = max(1, min(block, s))
    return b, (-s) % b


def _pad_seq(x, pad: int):
    """Zero-pad the sequence axis (axis 2 of [B, H, S, D] / [B, H, S, 1])."""
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[2] = (0, pad)
    return jnp.pad(x, widths)


def _mask_block(qp, kp, *, causal: bool, window: Optional[int],
                kv_len: int):
    """[bq, bk] validity mask from absolute q/k positions (qp/kp are
    broadcasted iotas). ``kv_len`` masks the padded KV tail."""
    mask = kp < kv_len
    if causal:
        mask &= kp <= qp
    if window is not None:
        mask &= kp > qp - window
    return mask


def _block_live(qp_lo, kp_lo, *, causal: bool, window: Optional[int],
                kv_len: int, bq: int, bk: int):
    """Scalar predicate: does block [qp_lo, qp_lo+bq) x [kp_lo, kp_lo+bk)
    contain ANY unmasked (q, k) pair? Exact for causal and/or window (a
    pair with kp <= qp and kp > qp - window exists iff kp_lo <= qp_hi and
    kp_hi > qp_lo - window) — lets the grid skip ~half the tiles on the
    causal path and all but O(window/bk) per row on the windowed path."""
    live = kp_lo < kv_len
    if causal:
        live &= kp_lo <= qp_lo + bq - 1
    if window is not None:
        live &= kp_lo + bk - 1 > qp_lo - window
    return live


# ------------------------------------------------------------- forward ----
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, scale: float, causal: bool, window: Optional[int],
                bq: int, bk: int, q_offset: int, kv_len: int):
    kv_i = pl.program_id(3)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qp_lo = q_offset + pl.program_id(2) * bq
    kp_lo = kv_i * bk

    @pl.when(_block_live(qp_lo, kp_lo, causal=causal, window=window,
                         kv_len=kv_len, bq=bq, bk=bk))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)    # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)    # [bk, d]
        v = v_ref[0, 0]                        # [bk, d]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale

        qp = qp_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kp = kp_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = _mask_block(qp, kp, causal=causal, window=window,
                           kv_len=kv_len)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                               # [bq, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
            p.astype(jnp.float32), v.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kv_i == pl.num_programs(3) - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(l)


def flash_attention(q, k, v, *, scale: Optional[float] = None,
                    causal: bool = True, window: Optional[int] = None,
                    q_offset: int = 0, block_q: int = 128,
                    block_k: int = 128, return_lse: bool = False,
                    interpret: bool = False):
    """q: [B, Hq, Sq, D]; k/v: [B, Hkv, Skv, D]. Returns [B, Hq, Sq, D]
    (and the float32 [B, Hq, Sq] row logsumexp when ``return_lse``)."""
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    bq, pq = _block_and_pad(block_q, sq)
    bk, pk = _block_and_pad(block_k, skv)
    q_, k_, v_ = _pad_seq(q, pq), _pad_seq(k, pk), _pad_seq(v, pk)
    spq, spk = sq + pq, skv + pk
    grid = (b, hq, spq // bq, spk // bk)

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               window=window, bq=bq, bk=bk,
                               q_offset=q_offset, kv_len=skv)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, qi, ki: (b_, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, qi, ki: (b_, h // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, qi, ki: (b_, h // g, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, qi, ki: (b_, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1),
                         lambda b_, h, qi, ki: (b_, h, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, spq, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, spq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention_fwd",
    )(q_, k_, v_)
    o, lse = o[:, :, :sq], lse[:, :, :sq, 0]
    return (o, lse) if return_lse else o


# --------------------------------------------------------- paged decode ----
# KV tokens one step of a lane's loop attends to: as many whole pages as
# fit in one MXU-wide tile, so P = DECODE_BLOCK_TOKENS // bs pages (at
# least one, at most the table's width).
DECODE_BLOCK_TOKENS = 128


def _token_scales(sc, bs: int, hkv: int, d: int):
    """int8 scales of a block's pages, [P, 1, bs * hkv] (lane ``t * hkv +
    h`` for token t, head h), spread to the [P * bs, hkv * d] multiplier
    of each token's row (lane ``h * d + e``). The spread is an exact
    0/1 matmul, so each multiplier is its scale bit for bit."""
    pages, _, n = sc.shape
    rows = jnp.broadcast_to(sc, (pages, bs, n)).reshape(pages * bs, n)
    tok = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0) % bs
    lane = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
    rows = jnp.where(lane // hkv == tok, rows, 0.0)
    spread = (jax.lax.broadcasted_iota(jnp.int32, (n, hkv * d), 0) % hkv
              == jax.lax.broadcasted_iota(jnp.int32, (n, hkv * d), 1) // d)
    return jax.lax.dot(rows, spread.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)


def _paged_decode_kernel(tbl_ref, ctx_ref, layer_ref, q_ref, k_hbm, *rest,
                         scale: float, bs: int, pages: int, t: int,
                         hkv: int, quantized: bool, latent_v: int):
    """One decode token per request against a paged KV pool.

    Grid (batch,): one step per lane, all heads at once. The pools stay
    in HBM (``pl.ANY``) as the engine stores them, the layer stacks [L,
    NB, bs, Hkv * D] with a page of every KV head per row block; the
    layer is scalar-prefetched (``layer_ref``), so the caller hands the
    stacks over whole. A ``fori_loop`` walks the lane's ``cdiv(ctx, P *
    bs)`` compute blocks. Each block's pages are copied from the
    scalar-prefetched block table (``tbl_ref[b * t + i]``), one DMA per
    page, into a double-buffered VMEM tile: block ``j + 1``'s copies start
    before block ``j`` is computed. Only live pages are copied: a ragged
    last block copies its live pages alone (the rest of its tile is
    stale, masked out of the scores and zeroed in V), table slots at or
    past the context are never read, and a lane with ``ctx == 0`` copies
    nothing and writes exact zeros. So the cost follows the pages the
    live lanes hold, not the table's width.

    The query arrives block-diagonal, [Hq, Hkv * D] with query head ``r``
    in the lanes of its KV head ``r // G``, so one matmul scores every
    head against the page rows. (m, l, acc) are the loop's carry: the
    float32 online-softmax recurrence of ``_fwd_kernel``. ``acc`` is
    [Hq, Hkv * D]; its diagonal blocks are the heads' outputs, folded to
    [Hq, D] at the end by an exact 0/1 matmul.

    ``latent_v`` > 0 is latent attention (MLA): one pool of rows that are
    the keys, whose first ``latent_v`` lanes are also the values, with
    one KV head. Each page is then copied once, and there is nothing to
    fold.
    """
    if latent_v:
        o_ref, k_buf, sem = rest
        pairs = ((k_hbm, k_buf),)
    elif quantized:
        v_hbm, ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, sem = rest
        pairs = ((k_hbm, k_buf), (v_hbm, v_buf), (ks_hbm, ks_buf),
                 (vs_hbm, vs_buf))
    else:
        v_hbm, o_ref, k_buf, v_buf, sem = rest
        pairs = ((k_hbm, k_buf), (v_hbm, v_buf))
    b = pl.program_id(0)
    layer = layer_ref[0]
    ctx = ctx_ref[b]
    n_pages = pl.cdiv(ctx, bs)
    n_blocks = pl.cdiv(ctx, pages * bs)
    hq, w = q_ref.shape[1:]
    g, d, blk = hq // hkv, w // hkv, pages * bs

    def each_live_page(j, slot, op):
        """Start or wait the copies of block ``j``'s live pages into
        buffer ``slot``; the table is read for live pages only."""
        for p in range(pages):
            @pl.when(j * pages + p < n_pages)
            def _page():
                page = tbl_ref[b * t + j * pages + p]
                for hbm, buf in pairs:
                    op(pltpu.make_async_copy(hbm.at[layer, page],
                                             buf.at[slot, p], sem.at[slot]))

    @pl.when(n_blocks > 0)
    def _first():
        each_live_page(0, 0, lambda c: c.start())

    q = q_ref[0].astype(jnp.float32)                   # [hq, w]

    def body(j, carry):
        m_prev, l_prev, acc_prev = carry
        slot = j % 2

        @pl.when(j + 1 < n_blocks)
        def _next():
            each_live_page(j + 1, 1 - slot, lambda c: c.start())

        each_live_page(j, slot, lambda c: c.wait())
        k = k_buf[slot].astype(jnp.float32).reshape(blk, w)
        if latent_v:
            v = k[:, :latent_v]
        else:
            v = v_buf[slot].astype(jnp.float32).reshape(blk, w)
        if quantized:
            k = k * _token_scales(ks_buf[slot], bs, hkv, d)
            v = v * _token_scales(vs_buf[slot], bs, hkv, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kp = j * blk + jax.lax.broadcasted_iota(jnp.int32, (hq, blk), 1)
        s = jnp.where(kp < ctx, s, NEG_INF)            # partial last page
        vp = j * blk + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        v = jnp.where(vp < ctx, v, 0.0)                # stale rows

        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=1, keepdims=True)
        acc_new = acc_prev * corr + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    init = (jnp.full((hq, 1), NEG_INF, jnp.float32),
            jnp.zeros((hq, 1), jnp.float32),
            jnp.zeros((hq, latent_v or w), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, n_blocks, body, init)
    if latent_v:
        o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        return
    o_ref[0] = (_fold_heads(acc, g, d) / jnp.maximum(l, 1e-30)).astype(
        o_ref.dtype)


def _fold_heads(acc, rows_per_kv: int, d: int):
    """[rows, Hkv * D] accumulator of a block-diagonal query -> [rows, D]:
    row ``r`` keeps the lanes of its KV head ``r // rows_per_kv``, summed
    down to D lanes by an exact 0/1 matmul."""
    rows, w = acc.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, w), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, w), 1)
    acc = jnp.where(row // rows_per_kv == lane // d, acc, 0.0)
    fold = (jax.lax.broadcasted_iota(jnp.int32, (w, d), 0) % d
            == jax.lax.broadcasted_iota(jnp.int32, (w, d), 1))
    return jax.lax.dot(acc, fold.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)


def _pool_geometry(q, k_pages, v_pages, k_scales, latent_v):
    """(Hkv, D) of page-major pools [L, NB, bs, Hkv * D] read by queries
    of D lanes, after checking the latent / K-V combination."""
    d, w = q.shape[-1], k_pages.shape[-1]
    if w % d:
        raise ValueError(f"pool rows of {w} lanes do not hold whole heads "
                         f"of {d}")
    hkv = w // d
    if (v_pages is None) != bool(latent_v) or (latent_v and (
            hkv != 1 or k_scales is not None)):
        raise ValueError("latent attention is one bf16 pool, one KV head, "
                         "and no V pool; a V pool needs latent_v=0")
    return hkv, d


def _block_diagonal(q, hkv: int, axis: int):
    """Queries [..., D] whose axis ``axis`` is the query head -> [...,
    Hkv * D], head ``h`` in the lanes of its KV head ``h // G`` and zeros
    elsewhere (one KV head: the queries as they are)."""
    if hkv == 1:
        return q
    hq, d = q.shape[axis], q.shape[-1]
    shape = [1] * (q.ndim - 1) + [hkv, 1]
    shape[axis] = hq
    own = (jnp.arange(hq)[:, None] // (hq // hkv)
           == jnp.arange(hkv)[None, :]).reshape(shape)
    return jnp.where(own, q[..., None, :], jnp.zeros((), q.dtype)).reshape(
        q.shape[:-1] + (hkv * d,))


def paged_decode_attention(q, k_pages, v_pages, block_tables, ctx_lens, *,
                           layer, scale: Optional[float] = None,
                           k_scales=None, v_scales=None,
                           latent_v: int = 0, interpret: bool = False):
    """Single-token decode attention over a paged KV cache.

    q: [B, Hq, D] (one query token per request); k_pages/v_pages: the
    page-major layer stacks [L, NB, bs, Hkv * D] as the engine stores
    them (a token's row holds every KV head), read at ``layer`` (an int32
    scalar, scalar-prefetched, so the stacks are passed whole and nothing
    slices a layer out of them); block_tables: [B, T] int32
    logical->physical maps (slots at or past a request's context are
    never read); ctx_lens: [B] int32 visible KV length per request
    (requests with ``ctx_lens == 0`` return zeros). With
    ``k_scales``/``v_scales`` ([L, NB, 1, bs * Hkv] float32, token ``t``
    of head ``h`` at lane ``t * Hkv + h``) the pools are int8 and
    dequantized in-kernel. Returns [B, Hq, D].

    Latent attention (MLA) passes ``v_pages=None`` and ``latent_v``: the
    one pool ([L, NB, bs, D], D the latent and its rotary key) holds the
    keys, and its first ``latent_v`` lanes are the values. Returns [B,
    Hq, latent_v] then.
    """
    b, hq, _ = q.shape
    hkv, d = _pool_geometry(q, k_pages, v_pages, k_scales, latent_v)
    bs = k_pages.shape[2]
    t = block_tables.shape[1]
    pages = max(1, min(t, DECODE_BLOCK_TOKENS // bs))
    scale = scale if scale is not None else d ** -0.5
    quantized = k_scales is not None

    qbd = _block_diagonal(q, hkv, axis=1)
    operands = [qbd, k_pages]
    scratch = [pltpu.VMEM((2, pages, bs, hkv * d), k_pages.dtype)]
    if not latent_v:
        operands.append(v_pages)
        scratch.append(pltpu.VMEM((2, pages, bs, hkv * d), v_pages.dtype))
    if quantized:
        operands += [k_scales, v_scales]
        scratch += [pltpu.VMEM((2, pages, 1, bs * hkv), jnp.float32)] * 2

    dv = latent_v or d
    kernel = functools.partial(_paged_decode_kernel, scale=scale, bs=bs,
                               pages=pages, t=t, hkv=hkv,
                               quantized=quantized, latent_v=latent_v)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, hq, hkv * d),
                               lambda b_, tbl, ctx, lay: (b_, 0, 0))]
        + [pl.BlockSpec(memory_space=pl.ANY)] * (len(operands) - 1),
        out_specs=pl.BlockSpec((1, hq, dv),
                               lambda b_, tbl, ctx, lay: (b_, 0, 0)),
        scratch_shapes=scratch + [pltpu.SemaphoreType.DMA((2,))],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(block_tables.astype(jnp.int32).reshape(-1),
      ctx_lens.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), *operands)


# --------------------------------------------------------- paged prefill ---
def _lane_group(hkv: int, d: int) -> int:
    """KV heads the prefill kernel serves together from one lane slice of
    a page: the fewest whose lanes fill whole 128-lane tiles (2 heads of
    64, 1 of 128 or of 640), or all of them where that does not divide
    ``hkv``. Mosaic slices a page's lanes only at whole tiles, and a
    group's query is block-diagonal within the group alone, so the
    kernel's work and VMEM follow Hq * C * D, not Hq * C * Hkv * D."""
    hp = 128 // math.gcd(d, 128)
    return hp if hkv % hp == 0 else hkv


def _paged_prefill_kernel(tbl_ref, meta_ref, q_ref, k_ref, *rest,
                          scale: float, bs: int, chunk: int, hkv: int,
                          hp: int, quantized: bool, latent_v: int):
    """One prompt chunk of a single request against a paged KV pool.

    Grid (table-slot,): the one dimension walks the request's block table
    sequentially while (m, l, acc) persist in VMEM scratch —
    ``_fwd_kernel``'s KV walk through a block table. Each step reads one
    page of the layer stacks [L, NB, bs, Hkv * D] (the layer comes with
    the scalar-prefetched ``meta``), a row of every KV head per token,
    and serves every query head from it, one lane group of ``hp`` KV
    heads (:func:`_lane_group`) at a time. The query chunk arrives as
    [Hkv / hp, hp * G * C, hp * D]: in group ``j``, row ``r`` is query
    head ``j * hp * G + r // C`` at chunk offset ``r % C`` (absolute
    position ``q_offset + r % C``), block-diagonal within the group (in
    the lanes of its KV head), so one matmul scores the group's heads
    against the group's lanes of the page. With ``hp > 1`` the
    accumulator's diagonal blocks, folded as in decode, are the outputs.
    The causal mask is applied from absolute positions against the
    page's absolute KV positions. The chunk's OWN K/V rows have already
    been written into their pool blocks before this kernel runs, so
    "prior context plus itself" is one uniform table walk — there is no
    contiguous [Smax] staging buffer anywhere. Blocks at or past ``ctx =
    q_offset + chunk_len`` are dead (their table entries point at the
    reserved null block) and skip compute entirely; rows of the chunk
    past ``chunk_len`` (last-chunk padding) are garbage by contract and
    masked down to a nonempty-but-meaningless context so they stay
    finite. With ``latent_v`` (MLA) there is no V pool: the values are
    the first ``latent_v`` lanes of the key rows, and one KV head leaves
    nothing to fold.
    """
    if latent_v:
        o_ref, m_ref, l_ref, acc_ref = rest
    elif quantized:
        v_ref, ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        v_ref, o_ref, m_ref, l_ref, acc_ref = rest
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_offset = meta_ref[0]
    ctx = meta_ref[1]
    groups, rows, gw = q_ref.shape
    d = gw // hp

    @pl.when(i * bs < ctx)
    def _compute():
        k = k_ref[0, 0].astype(jnp.float32)        # [bs, hkv * d]
        if latent_v:
            v = k[:, :latent_v]
        else:
            v = v_ref[0, 0].astype(jnp.float32)    # [bs, hkv * d]
        if quantized:                              # per-row absmax scales
            k = k * _token_scales(ks_ref[0], bs, hkv, d)
            v = v * _token_scales(vs_ref[0], bs, hkv, d)
        r = jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 0)
        qp = q_offset + r % chunk                  # absolute q position
        kp = i * bs + jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 1)
        live = (kp <= qp) & (kp < ctx)
        for j in range(groups):
            lanes = slice(j * gw, (j + 1) * gw)
            q = q_ref[j].astype(jnp.float32)       # [rows, gw]
            s = jax.lax.dot_general(q, k[:, lanes], (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(live, s * scale, NEG_INF)
            m_prev = m_ref[j]                      # [rows, 1]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[j] = l_ref[j] * corr + p.sum(axis=1, keepdims=True)
            acc_ref[j] = acc_ref[j] * corr + jax.lax.dot(
                p, v if latent_v else v[:, lanes],
                preferred_element_type=jnp.float32)
            m_ref[j] = m_new

    @pl.when(i == pl.num_programs(0) - 1)
    def _done():
        for j in range(groups):
            acc = acc_ref[j]
            if hp > 1:
                acc = _fold_heads(acc, rows // hp, d)
            o_ref[j] = (acc / jnp.maximum(l_ref[j], 1e-30)).astype(
                o_ref.dtype)


def paged_prefill_attention(q, k_pages, v_pages, block_table, q_offset,
                            ctx_len, *, layer,
                            scale: Optional[float] = None,
                            k_scales=None, v_scales=None, latent_v: int = 0,
                            interpret: bool = False):
    """Chunked-prefill attention for one request over a paged KV cache.

    q: [Hq, C, D] (a fixed-size query chunk whose row ``c`` sits at
    absolute position ``q_offset + c``); k_pages/v_pages: the page-major
    layer stacks [L, NB, bs, Hkv * D] ALREADY holding the chunk's own K/V
    rows, read at ``layer`` (int32 scalar, scalar-prefetched; the stacks
    are passed whole); block_table: [T] int32 logical->physical map (dead
    slots point at the reserved null block 0); q_offset/ctx_len: traced
    int32 scalars — ``ctx_len = q_offset + chunk_len`` is the visible KV
    length, so the same jitted call serves every chunk of every prompt
    length. With ``k_scales``/``v_scales`` ([L, NB, 1, bs * Hkv] float32)
    the pools are int8 and dequantized in-kernel. Rows past
    ``chunk_len`` are padding and return garbage (finite) values.
    Returns [Hq, C, D].

    Latent attention (MLA) passes ``v_pages=None`` and ``latent_v``: one
    pool [L, NB, bs, D] of key rows whose first ``latent_v`` lanes are the
    values; each page is read once. Returns [Hq, C, latent_v] then.
    """
    hq, c, _ = q.shape
    hkv, d = _pool_geometry(q, k_pages, v_pages, k_scales, latent_v)
    bs = k_pages.shape[2]
    t = block_table.shape[0]
    scale = scale if scale is not None else d ** -0.5
    quantized = k_scales is not None
    dv = latent_v or d
    hp = _lane_group(hkv, d)
    groups, rows, gw = hkv // hp, hq // hkv * hp * c, hp * d
    # [Hq, C, D] -> [groups, hp * G, C, hp * D] -> [groups, rows, gw]
    qg = _block_diagonal(q.reshape(groups, hq // groups, c, d), hp,
                         axis=1).reshape(groups, rows, gw)
    meta = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                      jnp.asarray(ctx_len, jnp.int32),
                      jnp.asarray(layer, jnp.int32)])

    kernel = functools.partial(_paged_prefill_kernel, scale=scale, bs=bs,
                               chunk=c, hkv=hkv, hp=hp, quantized=quantized,
                               latent_v=latent_v)
    page = pl.BlockSpec((1, 1, bs, hkv * d),
                        lambda i, tbl, meta_: (meta_[2], tbl[i], 0, 0))
    in_specs = [pl.BlockSpec((groups, rows, gw),
                             lambda i, tbl, meta_: (0, 0, 0)), page]
    operands = [qg, k_pages]
    if not latent_v:
        in_specs.append(page)
        operands.append(v_pages)
    if quantized:
        in_specs += [pl.BlockSpec(
            (1, 1, 1, bs * hkv),
            lambda i, tbl, meta_: (meta_[2], tbl[i], 0, 0))] * 2
        operands += [k_scales, v_scales]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(t,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((groups, rows, dv),
                               lambda i, tbl, meta_: (0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((groups, rows, 1), jnp.float32),
            pltpu.VMEM((groups, rows, 1), jnp.float32),
            pltpu.VMEM((groups, rows, latent_v or gw), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((groups, rows, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_prefill_attention",
    )(block_table.astype(jnp.int32), meta, *operands)
    return o.reshape(hq, c, dv)


# ------------------------------------------------------------ backward ----
def _bwd_preprocess_kernel(o_ref, do_ref, delta_ref):
    o = o_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    delta_ref[0, 0] = (o * do).sum(axis=1, keepdims=True)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *,
                    scale: float, causal: bool, window: Optional[int],
                    bq: int, bk: int, q_offset: int, kv_len: int,
                    q_len: int, nqb: int):
    i = pl.program_id(3)                       # (group, q-block) sequential

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    qrow_lo = (i % nqb) * bq
    qp_lo = q_offset + qrow_lo
    kp_lo = pl.program_id(2) * bk
    live = _block_live(qp_lo, kp_lo, causal=causal, window=window,
                       kv_len=kv_len, bq=bq, bk=bk)
    live &= qrow_lo < q_len                    # skip fully padded q tiles

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)    # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)    # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)    # [bk, d]
        do = do_ref[0, 0].astype(jnp.float32)  # [bq, d]
        lse = lse_ref[0, 0]                    # [bq, 1] f32
        delta = delta_ref[0, 0]                # [bq, 1] f32

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qrow = qrow_lo \
            + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        qp = q_offset + qrow
        kp = kp_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = _mask_block(qp, kp, causal=causal, window=window,
                           kv_len=kv_len)
        mask &= qrow < q_len                   # padded q tail contributes 0
        s = jnp.where(mask, s, NEG_INF)

        p = jnp.exp(s - lse)                   # [bq, bk], recomputed
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == pl.num_programs(3) - 1)
    def _done():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc, *,
                   scale: float, causal: bool, window: Optional[int],
                   bq: int, bk: int, q_offset: int, kv_len: int):
    kv_i = pl.program_id(3)

    @pl.when(kv_i == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    qp_lo = q_offset + pl.program_id(2) * bq
    kp_lo = kv_i * bk

    @pl.when(_block_live(qp_lo, kp_lo, causal=causal, window=window,
                         kv_len=kv_len, bq=bq, bk=bk))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qp = qp_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kp = kp_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = _mask_block(qp, kp, causal=causal, window=window,
                           kv_len=kv_len)
        s = jnp.where(mask, s, NEG_INF)

        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_acc[...] += jax.lax.dot(ds, k,
                                   preferred_element_type=jnp.float32)

    @pl.when(kv_i == pl.num_programs(3) - 1)
    def _done():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, do, *,
                        scale: Optional[float] = None, causal: bool = True,
                        window: Optional[int] = None, q_offset: int = 0,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool = False):
    """Gradients (dq, dk, dv) from the saved residuals ``(q, k, v, o,
    lse)`` and the output cotangent ``do`` — O(S·D) memory, no O(S²)
    temporaries."""
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    bq, pq = _block_and_pad(block_q, sq)
    bk, pk = _block_and_pad(block_k, skv)
    q_, o_, do_ = _pad_seq(q, pq), _pad_seq(o, pq), _pad_seq(do, pq)
    lse_ = _pad_seq(lse.astype(jnp.float32)[..., None], pq)
    k_, v_ = _pad_seq(k, pk), _pad_seq(v, pk)
    spq, spk = sq + pq, skv + pk
    nqb, nkb = spq // bq, spk // bk

    delta = pl.pallas_call(
        _bwd_preprocess_kernel,
        grid=(b, hq, nqb),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, qi: (b_, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, qi: (b_, h, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, 1),
                               lambda b_, h, qi: (b_, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, spq, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name="flash_attention_bwd_delta",
    )(o_, do_)

    # dK/dV: grid over KV blocks; the sequential inner dim walks the GQA
    # query group x q blocks, so each group's contribution accumulates
    # into the shared KV head's scratch.
    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, q_offset=q_offset, kv_len=skv, q_len=sq, nqb=nqb)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b, hkv, nkb, g * nqb),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d),
                         lambda b_, h, ki, i: (b_, h * g + i // nqb,
                                               i % nqb, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, ki, i: (b_, h, ki, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, ki, i: (b_, h, ki, 0)),
            pl.BlockSpec((1, 1, bq, d),
                         lambda b_, h, ki, i: (b_, h * g + i // nqb,
                                               i % nqb, 0)),
            pl.BlockSpec((1, 1, bq, 1),
                         lambda b_, h, ki, i: (b_, h * g + i // nqb,
                                               i % nqb, 0)),
            pl.BlockSpec((1, 1, bq, 1),
                         lambda b_, h, ki, i: (b_, h * g + i // nqb,
                                               i % nqb, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, ki, i: (b_, h, ki, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, ki, i: (b_, h, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, spk, d), k.dtype),
            jax.ShapeDtypeStruct((b, hkv, spk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(q_, k_, v_, do_, lse_, delta)

    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, q_offset=q_offset, kv_len=skv)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b, hq, nqb, nkb),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, qi, ki: (b_, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, qi, ki: (b_, h // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, qi, ki: (b_, h // g, ki, 0)),
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, qi, ki: (b_, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1),
                         lambda b_, h, qi, ki: (b_, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1),
                         lambda b_, h, qi, ki: (b_, h, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda b_, h, qi, ki: (b_, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, spq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(q_, k_, v_, do_, lse_, delta)

    return dq[:, :, :sq], dk[:, :, :skv], dv[:, :, :skv]
