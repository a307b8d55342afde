"""Pallas TPU kernel: a transposed grouped matrix product that adds to a
running sum, for the weight gradients of the dropless expert layer.

``out[e] += lhs[rows of e].T @ rhs[rows of e]`` for sorted rows whose first
``sum(group_sizes)`` belong, group by group, to the experts; ``lhs``
[M, K] and ``rhs`` [M, N] in the operand dtype (bf16 on the main path),
``out`` [E, K, N] float32. The output is aliased to the running sum going
in, and the grid runs over the row tiles in sorted order with each visit's
expert and tile prefetched as scalars, so

- an expert's [K-tile, N-tile] block stays in VMEM over all the row tiles
  that hold rows of it, is read once, added to in fp32 and written once:
  no product is rounded to the operand dtype on its way into the sum;
- **only the experts that have rows are visited.** The blocks of every
  other expert are neither read nor written and keep their sum through the
  alias, bit for bit. (``jax.experimental.pallas.ops.tpu.megablox.tgmm``,
  the design this follows, visits every expert once a call to zero or
  re-store its block: with a third to a half of the experts in a chunk
  that is the traffic this kernel exists to remove.)
- rows past the groups are never read into a product: a tile that lies
  whole inside one expert's rows goes to the MXU as it is, a tile that an
  expert shares (with the next expert or with the rows past the groups) is
  masked to that expert's rows first, both operands, so a NaN a grouped
  product left there reaches nothing.

``nn/moe.py::_held_bwd`` calls it twice a chunk (gate/up and down) inside
``jax.named_scope("smp/moe/experts")``; the kernels are named
``smp_grouped_wgrad`` and read so in a device trace. Interpret-mode
fallback for the CPU tests mirrors ``pallas_gelu.py`` (an ``interpret``
argument and the ``FORCE_INTERPRET`` hook).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Testing hook, mirroring pallas_gelu.FORCE_INTERPRET.
FORCE_INTERPRET = False

# Rows a grid step. A tile an expert shares with its neighbour is visited
# once for each, so the MXU's wasted share is about experts / row tiles of
# a call: shorter tiles waste less and pay the per-step overhead more
# often. On the v5e, at both expert cells' shapes, 512 beat 256 and 128
# (PERF.md §6, PR 32).
_TILE_ROWS = 512
# Most bytes of the fp32 [K-tile, N-tile] block. The pipeline holds it
# twice going in and twice coming out, with the product and the operand
# tiles beside them: about 32 MiB at this size, inside ``_VMEM_LIMIT``
# (a v5e core has 128 MiB; the compiler's default scope is 16).
_BLOCK_BYTES = 4 * 2**20
_VMEM_LIMIT = 48 * 2**20


def _col_tiles(k, n):
    """``(tk, tn)``: divisors of ``k`` and ``n`` in multiples of 128 whose
    fp32 block fits ``_BLOCK_BYTES``, those that read the operands least
    (a call reads ``lhs`` once for each N-tile and ``rhs`` once for each
    K-tile: ``1/tn + 1/tk`` of ``m x k x n``)."""
    divisors = lambda d: [t for t in range(128, d + 1, 128) if d % t == 0]  # noqa: E731
    return min(
        ((tk, tn) for tk in divisors(k) for tn in divisors(n)
         if tk * tn * 4 <= _BLOCK_BYTES),
        key=lambda t: 1 / t[0] + 1 / t[1])


def grouped_wgrad_ok(rows, k, n):
    """Dispatch precondition: whole row tiles, lane-aligned widths, and the
    kernel's target backend (TPU, or interpret-mode testing)."""
    if rows % _TILE_ROWS or k % 128 or n % 128:
        return False
    return jax.default_backend() == "tpu" or FORCE_INTERPRET


def _visits(group_sizes, m, tm):
    """The grid's last axis: one visit for each (expert, row tile) pair
    that shares a row, in sorted order. Returns each expert's row range
    (``starts``, ``ends``), each visit's expert and row tile (padded to the
    static bound ``m // tm + experts - 1``), and how many visits there
    are. An expert without rows has none."""
    experts = group_sizes.shape[0]
    group_sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first_tile = starts // tm
    tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first_tile + 1, 0)
    bound = m // tm + experts - 1
    expert = jnp.repeat(jnp.arange(experts, dtype=jnp.int32), tiles,
                        total_repeat_length=bound)
    nth = jnp.arange(bound, dtype=jnp.int32) - (jnp.cumsum(tiles) - tiles)[expert]
    tile = jnp.clip(first_tile[expert] + nth, 0, m // tm - 1)
    return starts, ends, expert, tile, jnp.sum(tiles)


def _kernel(starts, ends, expert, tile, lhs, rhs, acc, out, *, tm):
    v = pl.program_id(2)
    e = expert[v]
    first_of_expert = jnp.logical_or(v == 0, expert[jnp.maximum(v - 1, 0)] != e)
    row0 = tile[v] * tm
    start, end = starts[e], ends[e]
    whole = jnp.logical_and(start <= row0, row0 + tm <= end)

    def add(a, b):
        product = jax.lax.dot_general(
            a, b, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(first_of_expert)
        def _():
            out[...] = acc[...] + product

        @pl.when(jnp.logical_not(first_of_expert))
        def _():
            out[...] += product

    @pl.when(whole)
    def _():
        add(lhs[...], rhs[...])

    @pl.when(jnp.logical_not(whole))
    def _():
        row = row0 + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        keep = jnp.logical_and(row >= start, row < end)

        def masked(ref):
            # Through fp32: v5e's vector unit has no bf16 select.
            return jnp.where(keep, ref[...].astype(jnp.float32), 0.0).astype(
                ref.dtype)

        add(masked(lhs), masked(rhs))


def grouped_wgrad(lhs, rhs, group_sizes, acc, interpret=False):
    """``acc[e] + lhs[rows of e].T @ rhs[rows of e]``: [E, K, N] float32,
    in ``acc``'s buffer where the caller gives it up (a loop carry).
    ``lhs`` [M, K], ``rhs`` [M, N], ``group_sizes`` [E] integers whose sum
    is at most M; see the module docstring. Shapes as
    ``grouped_wgrad_ok`` wants them."""
    m, k = lhs.shape
    n = rhs.shape[1]
    experts = group_sizes.shape[0]
    assert rhs.shape[0] == m and acc.shape == (experts, k, n), (
        lhs.shape, rhs.shape, acc.shape)
    assert acc.dtype == jnp.float32 and lhs.dtype == rhs.dtype
    tm, (tk, tn) = _TILE_ROWS, _col_tiles(k, n)
    assert m % tm == 0, (m, tm)
    starts, ends, expert, tile, visits = _visits(group_sizes, m, tm)

    block = pl.BlockSpec(
        (None, tk, tn), lambda j, i, v, s, e, ex, t: (ex[v], i, j))
    operand_bytes = lhs.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct(acc.shape, jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, k // tk, visits),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda j, i, v, s, e, ex, t: (t[v], i)),
                pl.BlockSpec((tm, tn), lambda j, i, v, s, e, ex, t: (t[v], j)),
                block,
            ],
            out_specs=block,
        ),
        # Operand 6 (after the four scalar arrays, lhs and rhs) is ``acc``.
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k * (n // tn) + m * n * (k // tk))
            * operand_bytes + 2 * acc.size * 4),
        name="smp_grouped_wgrad",
        interpret=interpret or FORCE_INTERPRET,
    )(starts, ends, expert, tile, lhs, rhs, acc)


def reference_grouped_wgrad(lhs, rhs, group_sizes, acc):
    """jnp reference: the same sums from one-hot row masks, in fp32."""
    ends = jnp.cumsum(group_sizes)
    row = jnp.arange(lhs.shape[0])[None, :]
    mine = (row >= (ends - group_sizes)[:, None]) & (row < ends[:, None])
    lhs = jnp.where(mine[:, :, None], lhs.astype(jnp.float32)[None], 0.0)
    rhs = jnp.where(mine[:, :, None], rhs.astype(jnp.float32)[None], 0.0)
    return acc + jnp.einsum("emk,emn->ekn", lhs, rhs,
                            precision=jax.lax.Precision.HIGHEST)
