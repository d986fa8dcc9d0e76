"""Pallas TPU grouped matmul: each group of rows times its own weight.

The rows of ``lhs`` (M, K) are laid out group after group, and each group
starts on a tile boundary: group g holds rows [start_g, start_g + size_g)
with ``start = group_starts(size, tm)``, the running sum of the sizes
rounded up to whole tiles of ``tm`` rows.  So every tile of rows belongs to
one group, and a grid step computes one tile against one group's weight.

Two ``pallas_call``s, named so that a device trace shows them:

  * ``gmm``:  out[r] = lhs[r] @ rhs[g(r)]       rhs (G, K, N), or (G, N, K)
    with ``transpose_rhs`` (the input gradient of the product);
  * ``tgmm``: out[g] = sum over rows r of g of lhs[r]^T rhs[r]   -> (G, K, N),
    the weight gradient.

Rows that are not a group's (the padding up to its last tile, and every
row past the last group's tiles) give zero in ``gmm``'s output and nothing
to ``tgmm``'s.  Tiles that hold no group's rows are not visited: the grid's
row axis is as long as the tiles in use, a number computed on the device
from the sizes, and the tables of which group and which block of rows each
grid step takes are prefetched into scalar memory.  Consecutive steps of
one group keep its weight block, so the pipeline fetches each weight block
once per group.  ``grouped_matmul`` ties the two together with a
``custom_vjp``.

VMEM per step: a (tm, K) block of rows and a (K, tn) block of weight in
``gmm``, both double buffered, and the (tm, tn) output; ``tgmm`` holds
(tm, tk) and (tm, tn) blocks of rows and a (tk, tn) f32 accumulator.  ``tn``
and ``tk`` are the whole dimension unless a divisor that is a multiple of
128 keeps a weight block under ``BLOCK_BUDGET``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Bytes a double-buffered weight (or weight-gradient) block may take.
BLOCK_BUDGET = 16 * 2**20
#: Scoped VMEM a call may use: its blocks, double buffered, and temporaries.
VMEM_LIMIT = 64 * 2**20

_LANES = 128


def group_starts(group_sizes: jax.Array, tm: int) -> jax.Array:
    """First row of each group: the sizes before it, each rounded up to tiles."""
    padded = (group_sizes + tm - 1) // tm * tm
    return (jnp.cumsum(padded) - padded).astype(jnp.int32)


def _block(dim: int, other: int, itemsize: int) -> int:
    """The whole ``dim``, or the largest divisor of it that is a multiple of
    128 and keeps a double-buffered (other, block) block under the budget."""
    if 2 * dim * other * itemsize <= BLOCK_BUDGET:
        return dim
    fits = [b for b in range(_LANES, dim, _LANES)
            if dim % b == 0 and 2 * b * other * itemsize <= BLOCK_BUDGET]
    return max(fits) if fits else dim


def _tiles(group_sizes: jax.Array, tm: int, n_rows: int, visit_empty: bool):
    """(tile_group, tile_block, visits): the group and the block of ``tm``
    rows of each grid step, and how many steps there are.  With
    ``visit_empty`` an empty group gets one step, which computes nothing."""
    G = group_sizes.shape[0]
    tiles = (group_sizes + tm - 1) // tm
    if visit_empty:
        tiles = jnp.maximum(tiles, 1)
    length = n_rows // tm + (G if visit_empty else 0)
    tile_group = jnp.repeat(jnp.arange(G, dtype=jnp.int32), tiles, total_repeat_length=length)
    first_visit = jnp.cumsum(tiles) - tiles
    step = jnp.arange(length, dtype=jnp.int32)
    block = group_starts(group_sizes, tm)[tile_group] // tm + step - first_visit[tile_group]
    tile_block = jnp.clip(block, 0, n_rows // tm - 1).astype(jnp.int32)
    return tile_group, tile_block, jnp.sum(tiles).astype(jnp.int32)


def _rows_mask(block, group, starts, sizes, tm: int, width: int):
    """(tm, width) mask of the tile's rows that are the group's."""
    row = block * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, width), 0)
    return (row >= starts[group]) & (row < starts[group] + sizes[group])


def _gmm_kernel(tile_group, tile_block, starts, sizes, lhs_ref, rhs_ref, out_ref, *,
                tm: int, transpose_rhs: bool):
    i = pl.program_id(1)
    contract = ((1,), (1,)) if transpose_rhs else ((1,), (0,))
    acc = jax.lax.dot_general(
        lhs_ref[...], rhs_ref[...], (contract, ((), ())), preferred_element_type=jnp.float32
    )
    mine = _rows_mask(tile_block[i], tile_group[i], starts, sizes, tm, acc.shape[1])
    out_ref[...] = jnp.where(mine, acc, 0.0).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "transpose_rhs", "interpret"))
def gmm(lhs, rhs, group_sizes, *, tm: int = 128, transpose_rhs: bool = False,
        interpret: bool = True):
    """``lhs`` (M, K) rows by group, ``rhs`` (G, K, N) (or (G, N, K) with
    ``transpose_rhs``), ``group_sizes`` (G,) int32 -> (M, N) in lhs's dtype."""
    M, K = lhs.shape
    N = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    assert M % tm == 0, (M, tm)
    group_sizes = group_sizes.astype(jnp.int32)
    tile_group, tile_block, visits = _tiles(group_sizes, tm, M, visit_empty=False)
    starts = group_starts(group_sizes, tm)
    tn = _block(N, K, rhs.dtype.itemsize)

    def rows(n, i, tg, tb, st, sz):
        return tb[i], 0

    if transpose_rhs:
        weight = pl.BlockSpec((None, tn, K), lambda n, i, tg, tb, st, sz: (tg[i], n, 0))
    else:
        weight = pl.BlockSpec((None, K, tn), lambda n, i, tg, tb, st, sz: (tg[i], 0, n))
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(N // tn, visits),
            in_specs=[pl.BlockSpec((tm, K), rows), weight],
            out_specs=pl.BlockSpec((tm, tn), lambda n, i, tg, tb, st, sz: (tb[i], n)),
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=VMEM_LIMIT
        ),
        interpret=interpret,
        name="gmm",  # the HLO instruction's name (gmm.<n>) in a device trace
    )(tile_group, tile_block, starts, group_sizes, lhs, rhs)
    # Tiles past the last group's are never visited: their rows hold nothing.
    used = jnp.arange(M)[:, None] < visits * tm
    return jnp.where(used, out, jnp.zeros((), out.dtype))


def _tgmm_kernel(tile_group, tile_block, starts, sizes, lhs_ref, rhs_ref, out_ref, acc, *,
                 tm: int):
    v = pl.program_id(2)
    last = pl.num_programs(2) - 1
    g = tile_group[v]
    first_of_group = (v == 0) | (tile_group[jnp.maximum(v - 1, 0)] != g)
    last_of_group = (v == last) | (tile_group[jnp.minimum(v + 1, last)] != g)

    @pl.when(first_of_group)
    def _():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(sizes[g] > 0)
    def _():
        b = tile_block[v]
        x, y = lhs_ref[...], rhs_ref[...]
        # Selects in f32 (the VPU's type), then the product in the inputs' own.
        xs = jnp.where(_rows_mask(b, g, starts, sizes, tm, x.shape[1]), x.astype(jnp.float32), 0.0)
        ys = jnp.where(_rows_mask(b, g, starts, sizes, tm, y.shape[1]), y.astype(jnp.float32), 0.0)
        acc[...] += jnp.dot(
            xs.T.astype(x.dtype), ys.astype(y.dtype), preferred_element_type=jnp.float32
        )

    @pl.when(last_of_group)
    def _():
        out_ref[...] = acc[...].astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("num_groups", "out_dtype", "tm", "interpret")
)
def tgmm(lhs, rhs, group_sizes, *, num_groups: int, out_dtype, tm: int = 128,
         interpret: bool = True):
    """``lhs`` (M, K) and ``rhs`` (M, N), rows by group -> (G, K, N), each
    group's lhs^T rhs; zero for an empty group."""
    M, K = lhs.shape
    N = rhs.shape[1]
    assert M % tm == 0, (M, tm)
    group_sizes = group_sizes.astype(jnp.int32)
    tile_group, tile_block, visits = _tiles(group_sizes, tm, M, visit_empty=True)
    starts = group_starts(group_sizes, tm)
    tn = _block(N, K, 4)
    tk = _block(K, tn, 4)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(K // tk, N // tn, visits),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda k, n, v, tg, tb, st, sz: (tb[v], k)),
                pl.BlockSpec((tm, tn), lambda k, n, v, tg, tb, st, sz: (tb[v], n)),
            ],
            out_specs=pl.BlockSpec(
                (None, tk, tn), lambda k, n, v, tg, tb, st, sz: (tg[v], k, n)
            ),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((num_groups, K, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
        name="tgmm",  # the HLO instruction's name (tgmm.<n>) in a device trace
    )(tile_group, tile_block, starts, group_sizes, lhs, rhs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def grouped_matmul(lhs, rhs, group_sizes, tm: int, interpret: bool):
    """``gmm`` with its backward: the input gradient is ``gmm`` against the
    transposed weights, the weight gradient ``tgmm``."""
    return gmm(lhs, rhs, group_sizes, tm=tm, interpret=interpret)


def _grouped_matmul_fwd(lhs, rhs, group_sizes, tm, interpret):
    out = gmm(lhs, rhs, group_sizes, tm=tm, interpret=interpret)
    return out, (lhs, rhs, group_sizes)


def _grouped_matmul_bwd(tm, interpret, saved, g):
    lhs, rhs, group_sizes = saved
    g = g.astype(lhs.dtype)
    d_lhs = gmm(g, rhs, group_sizes, tm=tm, transpose_rhs=True, interpret=interpret)
    d_rhs = tgmm(lhs, g, group_sizes, num_groups=rhs.shape[0], out_dtype=rhs.dtype,
                 tm=tm, interpret=interpret)
    return d_lhs, d_rhs, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)
