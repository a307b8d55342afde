"""Pallas TPU kernel: sum a chunk's routed rows back to their tokens, in the
running fp32 sum of the dropless expert layer.

``acc[tokens[i]] += float32(rows[i]) (* weights[i])`` for the first
``sum(group_sizes)`` of the chunk's sorted rows; ``acc`` [N, D] float32,
``rows`` [R, D] in the operand dtype (bf16 on the main path), ``tokens`` [R]
int32, ``weights`` [R] float32 (the forward pass's combine weights; the
backward pass adds the rows' gradients as they are). The output is aliased
to the sum going in. XLA's scatter-add of the same rows reads, adds and
writes one row at a time, each waiting for the one before (228–237 ns a row
on the v5e at both expert cells' shapes: PERF.md §6, PR 38). Here

- the grid runs over tiles of ``_TILE_TOKENS`` tokens. A tile's fp32
  [tile, D] block of the sum passes through VMEM once a call: read, added
  to, written through the alias. The sum moves at the memory's pace, and
  that is the call's cost: it streams all of ``acc`` whatever the rows, so
  it pays where a chunk has rows for a fair share of the tokens
  (``row_scatter_add_ok`` asks);
- the chunk's rows stay whole in VMEM. Inside one expert's group the
  tokens rise strictly (``route_to_held``'s stable sort), so the rows of a
  group that fall in a tile are one contiguous run; the runs' starts are
  counted outside (``_run_starts``) and prefetched as scalars. A run is
  read in aligned blocks of 16 rows, converted to fp32, multiplied by the
  block's weights, and each row is added to its token's row of the block
  by a one-sublane dynamic load, add and store, in the rows' order: a
  token that several groups name is added to once for each;
- every term is fp32 before it is multiplied and added and the sum stays
  fp32: the arithmetic is the scatter-add's, term for term;
- rows past the groups belong to no run and are never added (a grouped
  product leaves NaN there); tokens the chunk does not name pass through
  VMEM unchanged, bit for bit.

``nn/moe.py`` calls it inside ``jax.named_scope("smp/moe/combine")`` once a
chunk in ``_held_fwd`` and once in ``_held_bwd``; the kernels are named
``smp_row_scatter_add`` and read so in a device trace. Interpret-mode
fallback for the CPU tests mirrors ``pallas_grouped_wgrad.py``.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Testing hook, mirroring pallas_grouped_wgrad.FORCE_INTERPRET.
FORCE_INTERPRET = False

# Tokens a grid step, at most. On the v5e 512 and 1,024 read alike and 256
# a little slower where the rows are dense (PERF.md §6, PR 38).
_TILE_TOKENS = 512
# Rows a block of a run: a bf16 tile's sublanes, so a block's start is
# aligned for every operand dtype.
_ROW_BLOCK = 16
# The rows whole (one buffer), the weights' column padded to a lane tile,
# and a block of the sum twice going in and twice coming out: 48 MiB at
# 6,144 rows of 2,304 (a v5e core has 128 MiB; the default scope is 16),
# with ``_VMEM_SPARE`` left for a converted block and the compiler's own.
_VMEM_LIMIT = 64 * 2**20
_VMEM_SPARE = 8 * 2**20
# Most bytes of the sum a call may stream for each row it adds (all of
# ``acc`` in and out, over the chunk's rows). XLA's scatter-add costs a row
# what about 135 KB of streaming does; at 64 KiB the kernel is twice as
# fast, and it reads 25 and 44 KB at the Mellum and SDAR cells' shapes.
# Laguna's 1,024-row chunks over 8,192 tokens of 3,072 would stream 197 KB
# a row, 1.7 times the scatter-add's cost: the kernel stands aside there.
_STREAM_BYTES_PER_ROW = 64 * 2**10


def _token_tile(n):
    """The largest tile of at most ``_TILE_TOKENS`` tokens, in whole fp32
    sublane tiles, that divides ``n``; None where there is none."""
    for tile in range(min(_TILE_TOKENS, n) // 8 * 8, 0, -8):
        if n % tile == 0:
            return tile
    return None


def _vmem_bytes(n, d, rows, itemsize):
    return (rows * d * itemsize + rows * 128 * 4
            + 4 * _token_tile(n) * d * 4)


def row_scatter_add_ok(n, d, rows, itemsize=2):
    """Dispatch precondition for a sum of ``n`` tokens by ``d`` and chunks
    of ``rows`` rows of ``itemsize`` bytes an element: whole row blocks, a
    lane-aligned width, a token tile, the rows and the blocks inside the
    VMEM asked for, enough rows for the stream of the sum to pay, and the
    kernel's target backend (TPU, or interpret-mode testing)."""
    if rows % _ROW_BLOCK or d % 128 or _token_tile(n) is None:
        return False
    if _vmem_bytes(n, d, rows, itemsize) > _VMEM_LIMIT - _VMEM_SPARE:
        return False
    if 2 * n * d * 4 > rows * _STREAM_BYTES_PER_ROW:
        return False
    return jax.default_backend() == "tpu" or FORCE_INTERPRET


def _run_starts(tokens, group_sizes, tile, tiles):
    """``starts`` [groups x tiles + 1]: rows ``starts[g * tiles + i]`` to
    ``starts[g * tiles + i + 1]`` are group ``g``'s rows whose tokens lie
    in token tile ``i``. Sorted by group and, inside one, by token, the
    rows' ``group * tiles + tile`` never falls, so a run's start is the
    count of rows with a smaller key; rows past the groups count for no
    run."""
    rows, groups = tokens.shape[0], group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    row = jnp.arange(rows, dtype=jnp.int32)
    # Both by comparing all pairs: one small fusion each, no loop.
    group = jnp.searchsorted(ends, row, side="right", method="compare_all")
    key = jnp.where(row < ends[-1], group * tiles + tokens // tile,
                    groups * tiles)
    runs = jnp.arange(groups * tiles + 1, dtype=jnp.int32)
    return jnp.searchsorted(
        key, runs, side="left", method="compare_all").astype(jnp.int32)


def _kernel(starts, tokens, rows, *refs, tile, tiles, groups):
    *weights, acc, out = refs          # the weights' column where given
    i = pl.program_id(0)
    first_token = i * tile
    out[...] = acc[...]

    def add_run(g, carry):
        start = starts[g * tiles + i]
        end = starts[g * tiles + i + 1]

        def add_block(b, carry):
            row0 = pl.multiple_of(b * _ROW_BLOCK, _ROW_BLOCK)
            block = rows[pl.ds(row0, _ROW_BLOCK), :].astype(jnp.float32)
            if weights:
                block = block * weights[0][pl.ds(row0, _ROW_BLOCK), :]
            for j in range(_ROW_BLOCK):
                row = row0 + j

                @pl.when(jnp.logical_and(row >= start, row < end))
                def _():
                    at = pl.ds(tokens[row] - first_token, 1)
                    out[at, :] = out[at, :] + block[j:j + 1, :]

            return carry

        return jax.lax.fori_loop(
            start // _ROW_BLOCK, (end + _ROW_BLOCK - 1) // _ROW_BLOCK,
            add_block, carry)

    jax.lax.fori_loop(0, groups, add_run, 0)


def row_scatter_add(acc, rows, tokens, group_sizes, weights=None,
                    interpret=False):
    """``acc`` with the chunk's rows added to their tokens' rows: [N, D]
    float32, in ``acc``'s buffer where the caller gives it up (a loop
    carry). ``rows`` [R, D], ``tokens`` [R] int32 rising strictly inside
    each group, ``group_sizes`` [G] integers whose sum is at most R,
    ``weights`` [R] float32 or None; see the module docstring. Shapes as
    ``row_scatter_add_ok`` wants them."""
    n, d = acc.shape
    r = rows.shape[0]
    groups = group_sizes.shape[0]
    assert acc.dtype == jnp.float32 and rows.shape == (r, d), (
        acc.shape, acc.dtype, rows.shape)
    assert tokens.shape == (r,) and r % _ROW_BLOCK == 0, (tokens.shape, r)
    tile = _token_tile(n)
    tiles = n // tile
    tokens = tokens.astype(jnp.int32)
    starts = _run_starts(tokens, group_sizes, tile, tiles)
    # One buffer each: their block never changes over the grid.
    operands = [rows] + ([] if weights is None
                         else [weights.astype(jnp.float32).reshape(r, 1)])
    whole = [pl.BlockSpec(a.shape, lambda i, s, t: (0, 0),
                          pipeline_mode=pl.Buffered(1)) for a in operands]
    block = pl.BlockSpec((tile, d), lambda i, s, t: (i, 0))
    return pl.pallas_call(
        functools.partial(_kernel, tile=tile, tiles=tiles, groups=groups),
        out_shape=jax.ShapeDtypeStruct(acc.shape, jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles,),
            in_specs=whole + [block],
            out_specs=block,
        ),
        # ``acc`` is the last operand, after the two scalar arrays.
        input_output_aliases={2 + len(operands): 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * r * d, transcendentals=0,
            bytes_accessed=2 * acc.size * 4 + rows.size * rows.dtype.itemsize),
        name="smp_row_scatter_add",
        interpret=interpret or FORCE_INTERPRET,
    )(starts, tokens, *operands, acc)


def reference_row_scatter_add(acc, rows, tokens, group_sizes, weights=None):
    """jnp reference: XLA's scatter-add of the masked fp32 terms."""
    valid = jnp.arange(rows.shape[0]) < jnp.sum(group_sizes)
    terms = jnp.where(valid[:, None], rows, 0).astype(jnp.float32)
    if weights is not None:
        terms = terms * jnp.where(valid, weights, 0.0)[:, None]
    return acc.at[tokens].add(terms)
