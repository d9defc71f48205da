"""Paged KV-cache manager: block-table allocation over a fixed pool.

The serving tier's memory model (vLLM-style paging, sized for the edge
AD-LLM of paper Fig. 2): physical KV storage is a fixed pool of
``num_blocks`` blocks of ``block_size`` tokens per layer, and each
in-flight request holds a *logical* view — a row of physical block ids —
so admission/eviction never copies or compacts KV state. Physical block
0 is reserved as the null block: dead table slots point at it, its
contents are garbage by design, and the paged kernel masks it out via
``ctx_lens``.

The pools are page-major, ``[L, NB, bs, Hkv * D]``: a token's row holds
every KV head, so a page is one contiguous tile that the paged kernels
copy as stored. The engine carries the whole stacks through its layer
scan, writes each layer's new rows in place at ``(layer, block,
offset)`` and donates them to each launch, so no launch copies a pool.

Two cache modes share the layout:

  * ``fp32``/model-dtype pools — K/V stored as written;
  * int8 pools — every (token, kv-head) row is quantized through the
    :mod:`repro.kernels.quantize` Pallas pair with a per-row absmax
    scale, stored alongside as float32, one row of ``bs * Hkv`` scales
    a page (``[L, NB, 1, bs * Hkv]``). Rows are zero-padded to
    the kernel's 128-lane layout (padding cannot change a row's absmax)
    and the random-bits input is pinned to 2**31 — ``floor(x + 0.5)`` —
    so cache quantization is deterministic round-to-nearest rather than
    stochastic: a cache entry must read back identically every step.

Host-side allocation (:class:`BlockAllocator`) is deliberately plain
Python — the scheduler calls it between jitted steps; everything that
touches tensors (:func:`init_pools`, :func:`write_prefill`,
:func:`append_token`) is pure and jit-safe.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence

import jax.numpy as jnp

from repro.config import ModelConfig
from repro.kernels import ops as kops
from repro.kernels.quantize import LANES

#: pinned random-bits word giving u = 0.5 — deterministic round-to-nearest
NEAREST_BITS = jnp.uint32(1 << 31)


@dataclasses.dataclass(frozen=True)
class PagedCacheSpec:
    """Pool geometry: ``num_blocks`` physical blocks (block 0 reserved as
    the null block) of ``block_size`` tokens; request tables are
    ``max_blocks_per_req`` wide; ``quantized`` selects int8 pools."""
    num_blocks: int
    block_size: int
    max_blocks_per_req: int
    quantized: bool = False

    def __post_init__(self):
        if self.num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is the null block)")
        if self.block_size < 1 or self.max_blocks_per_req < 1:
            raise ValueError("block_size/max_blocks_per_req must be >= 1")

    def blocks_needed(self, tokens: int) -> int:
        return -(-tokens // self.block_size)

    @property
    def max_tokens_per_req(self) -> int:
        return self.max_blocks_per_req * self.block_size

    @classmethod
    def for_requests(cls, slots: int, max_tokens: int, block_size: int = 16,
                     quantized: bool = False, headroom: int = 1
                     ) -> "PagedCacheSpec":
        """A pool sized so ``slots`` concurrent requests of up to
        ``max_tokens`` always fit, plus the null block and ``headroom``
        spare blocks."""
        per_req = -(-max_tokens // block_size)
        return cls(num_blocks=1 + slots * per_req + headroom,
                   block_size=block_size, max_blocks_per_req=per_req,
                   quantized=quantized)


class BlockAllocator:
    """Refcounted free-list allocator over the physical pool (host-side).

    Allocation is all-or-nothing: ``alloc(n)`` returns ``None`` when the
    pool cannot cover the whole request, so admission never strands a
    partially-allocated request. Block 0 never enters the free list.

    Every live block carries a reference count: ``alloc`` hands blocks
    out at refcount 1, ``share`` increments (prefix-cache sharing — a
    second request mapping the same physical template blocks), and
    ``release`` decrements, returning a block to the free list only when
    its count reaches zero. Releasing a block more times than it is
    currently held (in one call or across calls) raises — the double-free
    safety net predates refcounting and survives it. Shared blocks are
    read-only by contract; a writer must drop its share and copy first
    (copy-on-write, orchestrated by the scheduler via
    ``PagedEngine.copy_block``)."""

    def __init__(self, spec: PagedCacheSpec):
        self.spec = spec
        self._free: List[int] = list(range(spec.num_blocks - 1, 0, -1))
        self._refs: Dict[int, int] = {}

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return (self.spec.num_blocks - 1) - len(self._free)

    def refcount(self, block: int) -> int:
        """Current reference count of ``block`` (0 when free)."""
        return self._refs.get(block, 0)

    def alloc(self, n: int) -> Optional[List[int]]:
        if n > len(self._free) or n > self.spec.max_blocks_per_req:
            return None
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._refs[b] = 1
        return out

    def share(self, blocks: Sequence[int]) -> None:
        """Increment the refcount of already-live blocks (all-or-nothing:
        validates every id before touching any count)."""
        for b in blocks:
            if not 0 < b < self.spec.num_blocks:
                raise ValueError(f"block id {b} outside the pool")
            if self._refs.get(b, 0) < 1:
                raise ValueError(f"share of free block {b}")
        for b in blocks:
            self._refs[b] += 1

    def release(self, blocks: Sequence[int]) -> None:
        counts: Dict[int, int] = {}
        for b in blocks:
            if not 0 < b < self.spec.num_blocks:
                raise ValueError(f"block id {b} outside the pool")
            counts[b] = counts.get(b, 0) + 1
        for b, n in counts.items():
            if n > self._refs.get(b, 0):
                raise ValueError(f"double free of block {b}")
        for b, n in counts.items():
            self._refs[b] -= n
            if self._refs[b] == 0:
                del self._refs[b]
                self._free.append(b)


class PrefixCache:
    """Pod prefix registry: full-block token chains -> physical blocks.

    Fleet prompts are templated per pod (shared prefix + unique suffix),
    so the KV state of the template blocks is identical across a pod's
    requests — K/V rows are a pure function of the token prefix. The
    registry maps each *full* block of a finished prompt, keyed by the
    entire token prefix up to that block boundary (a collision-free
    realization of token-hash chaining: matching key m+1 implies key m
    matched), to the physical block holding its K/V. A later request
    walks its own prompt's chain, maps every hit via
    ``BlockAllocator.share`` instead of recomputing, and resumes chunked
    prefill at the first uncached token.

    Only blocks whose ``block_size`` tokens are all prompt tokens are
    ever registered — decode appends land at position >= len(prompt),
    i.e. in later blocks — so registered blocks are immutable for the
    lifetime of the registration. When a prompt is covered end-to-end by
    cached blocks the model still owes the last token's logits; the last
    matched block is returned as ``cow_src`` for the scheduler to
    copy-on-write (copy to a private block, drop the share) so the
    recompute of that final token never writes into a shared block.

    Entries are LRU-ordered; :meth:`evict` frees registry-only blocks
    (refcount 1) from the cold end when admission runs out of pool."""

    def __init__(self, allocator: BlockAllocator):
        self.allocator = allocator
        self._map: "OrderedDict[tuple, int]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.cached_tokens = 0
        self.shared_blocks = 0     # pool blocks a request mapped vs computed

    def __len__(self) -> int:
        return len(self._map)

    def _chain_keys(self, prompt: Sequence[int]):
        bs = self.allocator.spec.block_size
        for m in range(len(prompt) // bs):
            yield tuple(prompt[:(m + 1) * bs])

    def match(self, prompt: Sequence[int]):
        """Longest registered full-block prefix of ``prompt``.

        Returns ``(shared, cow_src, resume_pos)``: ``shared`` are the
        physical blocks to map read-only into the request's table (each
        already incref'd here), ``cow_src`` is the incref'd block the
        scheduler must copy-on-write when the whole prompt was covered
        (else None), and ``resume_pos`` is the first prompt position
        chunked prefill still has to compute."""
        blocks = []
        for key in self._chain_keys(prompt):
            b = self._map.get(key)
            if b is None:
                break
            blocks.append(b)
            self._map.move_to_end(key)
        if not blocks:
            self.misses += 1
            return [], None, 0
        cow_src = None
        bs = self.allocator.spec.block_size
        resume = len(blocks) * bs
        if resume == len(prompt):
            # Whole prompt cached; recompute only the final token for its
            # logits, through a private copy of its block.
            cow_src = blocks.pop()
            resume = len(prompt) - 1
        self.allocator.share(blocks + ([cow_src] if cow_src is not None
                                       else []))
        self.hits += 1
        self.cached_tokens += resume
        self.shared_blocks += len(blocks)   # the CoW copy is not a saving
        return blocks, cow_src, resume

    def insert(self, prompt: Sequence[int], table: Sequence[int]) -> None:
        """Register ``prompt``'s full blocks out of a finished prefill's
        ``table`` (logical order). Already-registered chains keep their
        existing block; new registrations hold one registry ref."""
        for m, key in enumerate(self._chain_keys(prompt)):
            if key in self._map:
                self._map.move_to_end(key)
                continue
            b = int(table[m])
            self.allocator.share([b])
            self._map[key] = b

    def evict(self, want_blocks: int) -> int:
        """Drop cold registry-only entries (refcount 1 — no live request
        shares them) until ``want_blocks`` blocks were freed or no entry
        is evictable. Returns the number freed."""
        freed = 0
        for key in list(self._map):
            if freed >= want_blocks:
                break
            b = self._map[key]
            if self.allocator.refcount(b) == 1:
                del self._map[key]
                self.allocator.release([b])
                freed += 1
        return freed

    @property
    def registered_blocks(self) -> int:
        return len(set(self._map.values()))


# ---------------------------------------------------------------- pools ----
def init_pools(cfg: ModelConfig, spec: PagedCacheSpec) -> Dict:
    """Layer-stacked physical pools, page-major: k/v [L, NB, bs, Hkv * D],
    each token one row of every KV head (head ``h`` in lanes ``h * D`` to
    ``(h + 1) * D``). That is the page the paged kernels read, so they
    take the stacks as stored and no launch relayouts them. int8 pools
    keep their float32 absmax scales beside, one row a page: k_scale /
    v_scale [L, NB, 1, bs * Hkv], token ``t`` of head ``h`` at lane ``t *
    Hkv + h``.

    Latent attention (MLA) keeps one row per token per layer instead,
    the normed latent beside the rotated shared key (``cfg.mla.row``
    values, zero-padded to :func:`latent_width`): ``latent`` [L, NB, bs,
    width], the leading dense layers first, which both of the engine's
    layer walks carry whole."""
    if cfg.latent:
        if spec.quantized:
            raise NotImplementedError(
                "an int8 latent pool is not supported (bf16 latent only)")
        return {"latent": jnp.zeros((cfg.num_layers, spec.num_blocks,
                                     spec.block_size, latent_width(cfg)),
                                    cfg.dtype)}
    lead = (cfg.num_layers, spec.num_blocks)
    shape = lead + (spec.block_size, cfg.num_kv_heads * cfg.hd)
    if spec.quantized:
        scales = lead + (1, spec.block_size * cfg.num_kv_heads)
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(scales, jnp.float32),
            "v_scale": jnp.zeros(scales, jnp.float32),
        }
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype)}


def latent_width(cfg: ModelConfig) -> int:
    """Lanes of a latent pool row: ``cfg.mla.row`` rounded up to whole
    128-lane tiles. Mosaic copies a page only where its lanes are whole:
    the decode kernel's page copy of a 576-wide row (Moonlight's 512 +
    64) is refused ("Slice shape ... must be aligned to tiling (128)"),
    and the TPU's memory layout pads such a row to 640 lanes anyway."""
    return -(-cfg.mla.row // LANES) * LANES


def quantize_rows(x):
    """Deterministic round-to-nearest int8 quantization of the trailing
    axis: x [..., D] float -> (q int8 [..., D], scale float32 [..., 1]).
    Rows are zero-padded to the kernel's 128-lane layout; padding is
    absmax-neutral so the scales are exactly those of the D-wide rows."""
    lead, d = x.shape[:-1], x.shape[-1]
    m = 1
    for n in lead:
        m *= n
    rows = x.reshape(m, d).astype(jnp.float32)
    if d < LANES:
        rows = jnp.pad(rows, ((0, 0), (0, LANES - d)))
    elif d > LANES:
        raise NotImplementedError(f"head_dim {d} > {LANES} lanes")
    bits = jnp.full((m, LANES), NEAREST_BITS, jnp.uint32)
    q, scale = kops.quantize_int8(rows, bits)
    return q[:, :d].reshape(x.shape), scale.reshape(lead + (1,))


def dequantize_rows(q, scale, dtype=jnp.float32):
    return (q.astype(jnp.float32) * scale).astype(dtype)


def _rows_at(pools: Dict, key: str, layer, phys, off) -> tuple:
    """Index of the stored rows of tokens ``(phys, off)`` [N] in
    ``pools[key]`` at ``layer`` (a slice for every layer): a K/V or latent
    row, or the Hkv scales of a token, lanes ``off * Hkv + h`` of its
    page's scale row ([N, Hkv])."""
    if key.endswith("_scale"):
        hkv = pools[key].shape[-1] // pools["k"].shape[2]
        lanes = off[:, None] * hkv + jnp.arange(hkv, dtype=jnp.int32)
        return (layer, phys[:, None], 0, lanes)
    return (layer, phys, off)


def write_prefill(pools: Dict, spec: PagedCacheSpec, k_layers, v_layers,
                  table_row) -> Dict:
    """Scatter one request's contiguous prefill K/V into its pool blocks.

    k_layers/v_layers: [L, Hkv, S, D] (S is the padded prefill buffer —
    rows past the true context length are garbage and stay masked by
    ``ctx_lens``), laid out page-major here as [L, S / bs, bs, Hkv * D];
    table_row: [T] int32, trailing entries null. Blocks beyond the
    request's allocation scatter into the null block, which is garbage
    by contract."""
    l, hkv, s, d = k_layers.shape
    bs = spec.block_size
    pad = (-s) % bs
    if pad:
        widths = ((0, 0), (0, 0), (0, pad), (0, 0))
        k_layers = jnp.pad(k_layers, widths)
        v_layers = jnp.pad(v_layers, widths)
    nb = (s + pad) // bs
    row = table_row[:nb]

    def pages(x):                      # [L, Hkv, nb*bs, e] -> [L, nb, bs, Hkv*e]
        e = x.shape[-1]
        return x.reshape(l, hkv, nb, bs, e).transpose(0, 2, 3, 1, 4).reshape(
            l, nb, bs, hkv * e)

    out = dict(pools)
    for name, x in (("k", k_layers), ("v", v_layers)):
        if spec.quantized:
            q, sc = quantize_rows(x)
            out[name] = pools[name].at[:, row].set(pages(q))
            out[name + "_scale"] = pools[name + "_scale"].at[:, row].set(
                pages(sc).reshape(l, nb, 1, bs * hkv))
        else:
            out[name] = pools[name].at[:, row].set(
                pages(x).astype(pools[name].dtype))
    return out


def gather_rows(pools: Dict, phys, off) -> Dict:
    """Snapshot pool rows at ``(phys, off)`` token positions (jit-safe).

    ``phys``/``off``: [N] int32 physical block ids and in-block offsets.
    Returns ``{key: [L, N, ...]}`` — the exact stored rows (int8 codes
    [L, N, Hkv * D] AND their scales [L, N, Hkv] in quantized mode), so a
    later :func:`scatter_rows` restores them bitwise. This is the
    speculative decoder's rollback snapshot: taken over a lane's draft
    window before the batched verify appends draft K/V, then written
    back over the rejected tail so the pools are indistinguishable from
    never having drafted."""
    return {key: p[_rows_at(pools, key, slice(None), phys, off)]
            for key, p in pools.items()}


def scatter_rows(pools: Dict, rows: Dict, phys, off) -> Dict:
    """Write :func:`gather_rows` snapshots back at ``(phys, off)``.

    Callers mask a *partial* restore by redirecting kept positions to the
    null block (``phys = where(rejected, phys, 0)``); duplicate scatters
    into block 0 are harmless by the null-block contract."""
    return {key: p.at[_rows_at(pools, key, slice(None), phys, off)].set(
        rows[key].astype(p.dtype)) for key, p in pools.items()}


def append_token(pools: Dict, spec: PagedCacheSpec, layer, k_tok, v_tok,
                 phys, off) -> Dict:
    """Write new tokens' K/V into ``layer`` of the layer-stacked pools.

    k_tok/v_tok: [N, Hkv, D], each token's rows, written as one ``Hkv *
    D`` pool row (int8 pools: quantized per (token, head), the Hkv
    scales into the page's scale row); pools: the whole stacks of
    :func:`init_pools`, which a layer scan carries so that XLA writes
    them in place; layer: int32 scalar; phys/off: [N] physical block id
    and in-block offset. Inactive rows point at (null, 0) — duplicate
    scatters there are harmless."""
    n, hkv, d = k_tok.shape
    out = dict(pools)
    for name, x in (("k", k_tok), ("v", v_tok)):
        if spec.quantized:
            q, sc = quantize_rows(x)
            out[name] = pools[name].at[layer, phys, off].set(
                q.reshape(n, hkv * d))
            key = name + "_scale"
            out[key] = pools[key].at[_rows_at(pools, key, layer, phys,
                                              off)].set(sc[..., 0])
        else:
            out[name] = pools[name].at[layer, phys, off].set(
                x.reshape(n, hkv * d).astype(pools[name].dtype))
    return out


def append_latent(pool, layer, row, phys, off):
    """Write latent rows into ``layer`` of a layer-stacked latent pool
    [L, NB, bs, width]: row [N, row] (zero-padded to the pool's width) at
    ``(phys, off)`` [N] (padding and dead lanes point at the null block,
    where duplicate writes are harmless)."""
    row = jnp.pad(row, ((0, 0), (0, pool.shape[-1] - row.shape[-1])))
    return pool.at[layer, phys, off].set(row.astype(pool.dtype))
