"""Grouped SwiGLU over the experts one chip holds, as a Pallas TPU kernel.

The rows arrive sorted by expert, each expert's rows padded to whole
tiles of ``tile`` rows, so every tile belongs to one expert
(``tile_expert``, scalar-prefetched). The weights are the layer stacks
``[L, E, ...]`` of every MoE layer, and ``layer`` (scalar-prefetched)
picks the one that runs: a caller's loop over the layers then hands the
stacks over as they lie, where a slice of one layer would be copied
before the call (a custom call reads its operands whole). Grid
``(tiles, f blocks)``: a step loads its tile of rows and one ``block_f``
wide block of its expert's three weight matrices and adds
``(silu(x·W_g) * (x·W_i))·W_o`` over that block into a float32
accumulator, which the tile's last step writes out. Where the weights of
a whole expert fit VMEM (Moonlight's and Qwen3-MoE's widths) the block
is the whole expert: consecutive tiles of one expert keep the same
weight blocks, which the pipeline does not fetch again, so each expert
that has rows is streamed once. Wider experts (DBRX's) are streamed once
per tile, as in JAX's megablox ``gmm``, on which this is modelled. Tiles
past the live ones (``n_live``) point at the last live tile's last
blocks, so they fetch nothing and only write zero rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: VMEM for the three double-buffered weight blocks of one step
WEIGHT_VMEM_BYTES = 48 << 20


def block_f(d: int, f: int, itemsize: int) -> int:
    """The widest block of the expert width ``f`` (all of it, or a
    multiple of 128 lanes dividing it) whose three double-buffered
    weight blocks fit ``WEIGHT_VMEM_BYTES``."""
    def fits(b):
        return 2 * 3 * d * b * itemsize <= WEIGHT_VMEM_BYTES
    for b in [f] + [b for b in range(f - f % 128, 0, -128) if f % b == 0]:
        if fits(b):
            return b
    raise ValueError(
        f"moe_expert_ffn: no block of the expert width {f} (all of it or a "
        f"multiple of 128 dividing it) fits {WEIGHT_VMEM_BYTES >> 20} MiB "
        f"of VMEM at d_model {d}")


def _ffn_kernel(te_ref, n_ref, l_ref, x_ref, wi_ref, wg_ref, wo_ref, o_ref,
                acc_ref):
    # one MXU pass, exact for bf16 operands; stated so that a process-wide
    # default precision (``highest``) does not reach Mosaic, which refuses
    # it for bf16 ("Bad lhs type")
    dot = functools.partial(jax.lax.dot, precision=jax.lax.Precision.DEFAULT,
                            preferred_element_type=jnp.float32)
    live = pl.program_id(0) < n_ref[0]
    j, last = pl.program_id(1), pl.num_programs(1) - 1

    @pl.when(live & (j == 0))
    def _start():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _block():
        x = x_ref[...]
        h = (jax.nn.silu(dot(x, wg_ref[0, 0])) * dot(x, wi_ref[0, 0])
             ).astype(x.dtype)
        acc_ref[...] += dot(h, wo_ref[0, 0])

    @pl.when(j == last)
    def _out():
        o_ref[...] = jnp.where(live, acc_ref[...], 0.0).astype(o_ref.dtype)


def moe_expert_ffn(x, tile_expert, n_live, layer, wi, wg, wo, *,
                   tile: int, interpret: bool = False):
    """x: [M, d] rows sorted by expert, each expert's padded to ``tile``
    (M a multiple of it); tile_expert: [M / tile] int32, the held expert
    of each tile; n_live: [1] int32, the tiles that hold rows; layer: [1]
    int32; wi/wg: [L, E, d, f], wo: [L, E, f, d]. Returns [M, d]; rows of
    dead tiles are zero."""
    m, d = x.shape
    f = wi.shape[-1]
    n_tiles = m // tile
    itemsize = jnp.dtype(wi.dtype).itemsize
    bf = block_f(d, f, itemsize)
    n_f = f // bf

    def live(i, n):
        return jnp.minimum(i, jnp.maximum(n[0] - 1, 0))

    def fj(i, j, n):
        return jnp.where(i < n[0], j, n_f - 1)

    def w_in(i, j, te, n, lay):
        return (lay[0], te[live(i, n)], 0, fj(i, j, n))

    def w_out(i, j, te, n, lay):
        return (lay[0], te[live(i, n)], fj(i, j, n), 0)

    # three weight blocks and the row tiles, double-buffered, and the
    # accumulator
    vmem = (2 * (3 * d * bf * itemsize + 2 * tile * d * itemsize)
            + tile * d * 4 + (8 << 20))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_tiles, n_f),
        in_specs=[
            pl.BlockSpec((tile, d),
                         lambda i, j, te, n, lay: (live(i, n), 0)),
            pl.BlockSpec((1, 1, d, bf), w_in),
            pl.BlockSpec((1, 1, d, bf), w_in),
            pl.BlockSpec((1, 1, bf, d), w_out),
        ],
        out_specs=pl.BlockSpec((tile, d), lambda i, j, te, n, lay: (i, 0)),
        scratch_shapes=[pltpu.VMEM((tile, d), jnp.float32)],
    )
    return pl.pallas_call(
        _ffn_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(vmem)),
        interpret=interpret,
        name="moe_expert_ffn",
    )(tile_expert.astype(jnp.int32), n_live.astype(jnp.int32),
      layer.astype(jnp.int32), x, wi, wg, wo)


def moe_expert_ffn_ref(x, tile_expert, n_live, layer, wi, wg, wo, *,
                       tile: int):
    """The same computation in plain XLA ops (tests; the CPU)."""
    m, d = x.shape
    xt = x.reshape(m // tile, tile, d)
    e = tile_expert
    wi, wg, wo = wi[layer[0]], wg[layer[0]], wo[layer[0]]
    g = jnp.einsum("ntd,ndf->ntf", xt, wg[e],
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("ntd,ndf->ntf", xt, wi[e],
                   preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    y = jnp.einsum("ntf,nfd->ntd", h, wo[e],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    live = jnp.arange(m // tile) < n_live[0]
    return jnp.where(live[:, None, None], y, 0).reshape(m, d)
