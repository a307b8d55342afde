"""Pallas TPU flash-attention kernels (forward + backward).

Parity target: the reference's fused attention-softmax CUDA kernel PAIRS
(``smp_torch_cuda_lib``: ``scaled_masked_softmax_{forward,backward}``,
``scaled_upper_triang_softmax_{forward,backward}`` — SURVEY §2.1 N8,
dispatched from ``torch/nn/softmax.py:7-93``). The TPU design goes further
than the reference's fused softmax: a blockwise online-softmax (flash)
forward and a blockwise recompute backward, neither of which materializes
the [T, S] score matrix in HBM — scores live in VMEM one
[block_q, block_k] tile at a time.

Supported feature surface (all combinations):
  - causal and non-causal attention, T != S (cross-attention offsets);
  - windowed (local/banded) attention, causal band or symmetric band
    (reference ``torch/nn/transformer.py:1331-1352``);
  - the block-diffusion mask (``block_diffusion=B``) over a two-copy
    stream [noisy ; clean] of 2L positions cut into blocks of B: a noisy
    row sees its own noisy block (both ways) and the clean blocks before
    it, a clean row the clean blocks up to its own, nothing else (the
    definition is at ``_bd_mask``). The mask is made from the indices in
    the kernel, and a program steps only into tiles that hold a live
    pair: its keys (or, in the dkv pass, its queries) lie in two disjoint
    ranges of tiles (``_bd_kv_ranges``, ``_bd_q_ranges``), walked one
    after the other by the same tile body, so about a quarter of the
    (2L)^2 pairs' tiles are visited at any L that B divides and any tile
    sizes. ``smp_flash_tiles_visited{pass}`` and
    ``smp_flash_tiles_live{pass}`` count both for each call traced;
  - whole tiles walked with no mask, under the block-diffusion mask: five
    of six tiles a pass visits there hold no dead pair (480 of 576 a head
    at 2 x 8,192 positions), and making ``_bd_mask`` and selecting by it
    is a fifth to a third of a tile's time on the chip. A tile is whole if
    EVERY pair of it is live under the call's mask, padding included: its
    keys are all clean and before the least reach of its rows, which lie
    in one copy. A tile among the noisy rows' own blocks, on the clean
    prefix's diagonal, in the padding, with keys of both copies or under a
    q tile that straddles the copies' middle is an edge tile. Each range
    of tiles a program walks is cut into leading edge tiles, whole tiles
    and trailing edge tiles (``_split``, from ``_bd_kv_whole`` and
    ``_bd_q_whole``: integer arithmetic on the tile index beside the
    ranges'), walked in order with one carry by one tile body whose
    static ``masked`` argument leaves out the iotas, compares and select
    on the whole ones; a select by an all-true mask returns its first
    operand, so the results are the masked walk's bit for bit. The causal
    and band masks are a few compares a tile (0-9% of a kernel's time
    with all of them compiled out, less than a second loop costs a
    program at 1,024 positions), so those walks keep every tile masked,
    as do calls whose global ids decide at run time (the cp ring).
    ``smp_flash_tiles_whole{pass}`` and ``smp_flash_tiles_masked{pass}``
    count a head's tiles of each kind for every call traced under a
    static mask, from the ranges the programs walk;
  - additive key-padding bias [B, S] (the broadcastable form of HF-style
    attention masks; arbitrary [.., T, S] biases fall back to jnp);
  - dropout on the attention probabilities, replayed exactly in the
    backward via a counter-based hash RNG (no [T, S] mask materialized);
  - fp32 score math always (subsumes ``attention_in_fp32``): MXU dots run
    on the input dtype with fp32 accumulation (exact for bf16 inputs) and
    masking/softmax/rescaling stay fp32; fp32 probability/gradient tiles
    are rounded to the operand dtype for the second-stage dots (standard
    flash practice — keeps every matmul at native MXU throughput).

Backward: two passes — dq (grid over q blocks, kv streamed) and dk/dv
(grid over kv blocks, q streamed) — using the forward's saved per-row
logsumexp and the precomputed ``delta = rowsum(dO * O)``, the standard
flash-attention backward decomposition. Each pass lays its tile so that
the products it accumulates take the tile as their left operand, as it
comes. The forward and the dq pass hold rows down the tile, [block_q,
block_k]: ``s = q k^T``, then ``acc += p v`` and ``dq += ds k``
contract over the keys, the tile's lanes. The dkv pass holds keys down
the tile, [block_k, block_q]: ``s^T = k q^T`` and ``dp^T = v dO^T`` (the
form ``s`` has), then ``dv += p^T dO`` and ``dk += ds^T q`` contract over
the rows, again the tile's lanes. Rows-major there, both would contract
over their left operand's rows, and the compiler turned ``p`` and ``ds``
in the transpose unit for it: 1,090 operations a [256, 512] tile, a
sixth of the pass's time on the chip (PERF.md section 6, PR 50). ``lse``
and ``delta`` lie in HBM as rows of lanes, [B*H, 1, T]: the dkv pass
reads its [1, block_q] slice as it lies, where the dq pass turns its own
into a [block_q, 1] column once a program; what belongs to a dkv
program's own keys (its slice of the key-padding bias, its global ids)
is made a [block_k, 1] column once a program. Every mask and the dropout
hash are functions of the pair (row, key), so the keys-major tile's are
the rows-major tile's transposed (``keys_major`` in ``_tile_keep``).

Layout: inputs [B, T, H, hd]; kernels run on [B*H, T, hd].

Value heads of their own size: v (and so o, dO and dv) may be
[.., hd_v] wide where q and k are [.., hd] (latent attention: 192 against
128). Each is padded to its own lane width (``_pad_width``) and the blocks
of v, o, dO and dv take the values' width, so narrower values cost no
padded product; with hd_v == hd every shape and block is what it was.

Grouped KV heads: k and v may hold fewer heads than q ([B, S, H_kv, hd],
H a multiple of H_kv); query head h reads KV head h // (H / H_kv). The
forward and dq kernels reach the shared K/V block through their index map
(no repeated copy in HBM); the dkv kernel writes each query head's
contribution in fp32 and the group is summed outside it. With H_kv == H
every index map and shape is the multi-head one.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from smdistributed_modelparallel_tpu.parallel.memory import (
    FLASH_LSE_NAME,
    FLASH_OUT_NAME,
)

NEG_INF = -1e30
_LSE_MASKED = 1e30  # lse sentinel for fully-masked rows -> p == 0 in bwd

# Testing hook: run kernels in interpret mode even when dispatched through
# attention_core (which does not thread an interpret flag). Lets CPU tests
# exercise the real dispatch path.
FORCE_INTERPRET = False


def _dropout_keep(seed, bh, rows, cols, s_total, rate):
    """Counter-based keep mask for a [bq, bk] tile.

    lowbias32-style integer hash of the global (bh, row, col) position —
    identical bits in forward and backward, works compiled and in
    interpret mode (no pltpu PRNG state).
    """
    idx = (bh.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
           + rows.astype(jnp.uint32) * jnp.uint32(s_total)
           + cols.astype(jnp.uint32))
    x = idx + seed.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    thr = jnp.uint32(min(int(rate * 4294967296.0), 4294967295))
    return x >= thr


def _tile_mask(rows, cols, *, q_len, kv_len, causal, window):
    """Static structural mask for a tile given absolute row/col indices."""
    offset = kv_len - q_len
    keep = cols < kv_len
    keep &= rows < q_len
    if causal:
        keep &= cols <= rows + offset
        if window is not None:
            keep &= rows + offset - cols < window
    elif window is not None:
        keep &= jnp.abs(rows + offset - cols) < window
    return keep


def _kv_bounds(q_lo, q_hi, *, q_len, kv_len, causal, window, block_k, num_kv,
               xp=jnp):
    """Traced [lo, hi) kv-block range relevant to q rows [q_lo, q_hi)
    (``xp``: ``jnp`` on a program's traced indices, ``numpy`` for the
    host's count, here and in every range function below)."""
    offset = kv_len - q_len
    if causal:
        hi = xp.minimum(num_kv, (q_hi - 1 + offset) // block_k + 1)
    elif window is not None:
        # Symmetric band: cols < rows + offset + window.
        hi = xp.minimum(num_kv, (q_hi - 1 + offset + window - 1) // block_k + 1)
    else:
        hi = num_kv
    if window is not None:
        lo = xp.maximum(0, (q_lo + offset - window + 1) // block_k)
    else:
        lo = 0
    return lo, hi


def _q_bounds(k_lo, k_hi, *, q_len, kv_len, causal, window, block_q, num_q,
              xp=jnp):
    """Traced [lo, hi) q-block range relevant to kv cols [k_lo, k_hi)."""
    offset = kv_len - q_len
    lo = 0
    hi = num_q
    if causal:
        lo = xp.maximum(0, (k_lo - offset) // block_q)
        if window is not None:
            hi = xp.minimum(num_q, (k_hi - 1 - offset + window - 1) // block_q + 1)
    elif window is not None:
        lo = xp.maximum(0, (k_lo - offset - window + 1) // block_q)
        hi = xp.minimum(num_q, (k_hi - 1 - offset + window - 1) // block_q + 1)
    return lo, hi


# ----------------------------------------------------------------------
# The block-diffusion mask over a two-copy stream
# ----------------------------------------------------------------------
#
# 2 x half positions: [0, half) the noisy copy, [half, 2 half) the clean
# one; position i stands at sequence position i mod half, in block
# (i mod half) // blk. Query i sees key j iff
#     noisy on noisy:  same block;
#     noisy on clean:  the key's block is before the query's;
#     clean on clean:  the key's block is not after the query's;
#     clean on noisy:  never.


def _bd_mask(q_offset, k_offset, block_q, block_k, *, half, blk,
             keys_major=False):
    """The [block_q, block_k] mask of the tile whose first row and first
    key stand at ``q_offset`` and ``k_offset`` (whatever lies at or past
    2 x half is padding); ``keys_major``: its transpose, [block_k,
    block_q], from the same arithmetic on a [1, block_q] row of query
    indices and a [block_k, 1] column of key indices."""
    q_axis = int(keys_major)
    q_shape, k_shape = (
        ((1, block_q), (block_k, 1)) if keys_major
        else ((block_q, 1), (1, block_k)))
    rows = q_offset + jax.lax.broadcasted_iota(jnp.int32, q_shape, q_axis)
    cols = k_offset + jax.lax.broadcasted_iota(
        jnp.int32, k_shape, 1 - q_axis)
    r_clean, c_clean = rows >= half, cols >= half
    r_pos = jnp.where(r_clean, rows - half, rows)
    c_pos = jnp.where(c_clean, cols - half, cols)
    r_start = r_pos - r_pos % blk                  # the row's block starts
    # Clean keys: before the row's block (noisy row) or through it (clean).
    reach = r_start + jnp.where(r_clean, blk, 0)
    # Noisy keys: the row's own block, for a noisy row (no key is at or
    # past 2 x half, where a clean row's range is put). Selects are of
    # integers on a row or a column; the tile sees compares, and, or.
    own = jnp.where(r_clean, 2 * half, r_start)
    keep = (c_clean & (c_pos < reach)) | (
        ~c_clean & (c_pos >= own) & (c_pos < own + blk))
    return keep & (rows < 2 * half) & (cols < 2 * half)


def _tiles_of(lo, hi, block, xp):
    """The tiles of ``block`` positions that [lo, hi) touches, as a
    [first, past-the-last) pair; (0, 0) for an empty interval."""
    live = hi > lo
    return (xp.where(live, lo // block, 0),
            xp.where(live, (hi - 1) // block + 1, 0))


def _bd_kv_ranges(q_lo, q_hi, *, half, blk, block_k, xp=jnp):
    """The two [lo, hi) ranges of kv tiles that hold a key some query row
    of [q_lo, q_hi) sees: the noisy rows' own blocks, then the clean
    prefix (noisy rows: the blocks before theirs; clean rows: through
    theirs). Every tile of both holds a live pair; the second starts
    where the first ends if they would share a tile. ``xp``: ``jnp`` on
    a program's traced indices, ``numpy`` for the host's count."""
    n_hi = xp.minimum(q_hi, half)                  # noisy rows [q_lo, n_hi)
    noisy = q_lo < n_hi
    last_start = (n_hi - 1) // blk * blk           # last noisy row's block
    a_lo, a_hi = _tiles_of(
        xp.where(noisy, q_lo // blk * blk, 0),
        xp.where(noisy, last_start + blk, 0), block_k, xp)
    c_lo, c_hi = xp.maximum(q_lo, half), xp.minimum(q_hi, 2 * half)
    reach = xp.maximum(
        xp.where(noisy, last_start, 0),
        xp.where(c_lo < c_hi, (c_hi - 1 - half) // blk * blk + blk, 0))
    b_lo, b_hi = _tiles_of(half, half + reach, block_k, xp)
    b_lo = xp.maximum(b_lo, a_hi)
    return (a_lo, a_hi), (b_lo, xp.maximum(b_hi, b_lo))


def _bd_q_ranges(k_lo, k_hi, *, half, blk, block_q, xp=jnp):
    """The two [lo, hi) ranges of q tiles that hold a row seeing some key
    of [k_lo, k_hi): noisy rows (the noisy keys' own blocks; for clean
    keys every block after the first key's), then clean rows (from the
    first clean key's block on)."""
    n_hi = xp.minimum(k_hi, half)                  # noisy keys [k_lo, n_hi)
    noisy = k_lo < n_hi
    c_lo = xp.maximum(k_lo, half)                  # clean keys [c_lo, c_hi)
    clean = c_lo < xp.minimum(k_hi, 2 * half)
    first_start = (c_lo - half) // blk * blk       # first clean key's block
    after = xp.where(clean & (first_start + blk < half),
                     first_start + blk, half)      # noisy rows [after, half)
    own_lo = xp.where(noisy, k_lo // blk * blk, half)
    own_hi = xp.where(noisy, (n_hi - 1) // blk * blk + blk, 0)
    a_lo, a_hi = _tiles_of(
        xp.minimum(own_lo, after),
        xp.where(after < half, half, own_hi), block_q, xp)
    b_lo, b_hi = _tiles_of(
        xp.where(clean, half + first_start, 0),
        xp.where(clean, 2 * half, 0), block_q, xp)
    b_lo = xp.maximum(b_lo, a_hi)
    return (a_lo, a_hi), (b_lo, xp.maximum(b_hi, b_lo))


def _bd_kv_whole(q_lo, q_hi, *, half, blk, block_k, xp=jnp):
    """[lo, hi) of the kv tiles every pair of which is live for the rows
    [q_lo, q_hi): tiles of clean keys alone that end before the least
    reach of the rows (their first row's: its block's start if noisy, its
    block's end if clean), for rows of one half; none for rows on both
    sides of ``half`` or in the padding. They lie in the clean-prefix
    range of ``_bd_kv_ranges``; the noisy rows' own blocks are all edge."""
    clean = q_lo >= half
    start = xp.where(clean, q_lo - half, q_lo) // blk * blk
    lo = -(-half // block_k)
    hi = (half + start + xp.where(clean, blk, 0)) // block_k
    one_half = (q_hi <= half) | (clean & (q_hi <= 2 * half))
    return lo, xp.where(one_half, hi, lo)


def _bd_q_whole(k_lo, k_hi, *, half, blk, block_q, xp=jnp):
    """``_bd_kv_whole``'s mirror: for the keys [k_lo, k_hi) the [lo, hi) of
    whole q tiles among the noisy rows, then among the clean rows (one for
    each range of ``_bd_q_ranges``). Both run to their half's last whole
    tile, from the first row whose reach passes the last key: the start of
    the block after that key's for a noisy row, of that key's own for a
    clean one. None (an empty range at that end) unless every key is
    clean."""
    clean = (k_lo >= half) & (k_hi <= 2 * half)
    after = -(-(k_hi - half) // blk) * blk         # the block after the last key's
    n_hi, c_hi = half // block_q, 2 * half // block_q
    n_lo = xp.where(clean, -(-after // block_q), n_hi)
    c_lo = xp.where(clean, -(-(half + after - blk) // block_q), c_hi)
    return (n_lo, n_hi), (c_lo, c_hi)


def _bd_live_tiles(half, blk, block_q, block_k, t_pad):
    """Tiles of one head's [t_pad, 2 x half] pairs that hold a live one
    over the two-copy stream, counted from the mask's definition row by
    row (a row's keys are at most two intervals), independently of the
    ranges the programs walk."""
    T = 2 * half
    num_q, num_kv = t_pad // block_q, -(-T // block_k)
    live = np.zeros((num_q, num_kv + 1), np.int64)
    rows = np.arange(T)
    pos = rows % half
    start = pos - pos % blk
    noisy = rows < half
    for lo, hi in (
        (np.where(noisy, start, 0), np.where(noisy, start + blk, 0)),
        (np.full(T, half), half + start + np.where(noisy, 0, blk)),
    ):
        t_lo, t_hi = _tiles_of(lo, hi, block_k, np)
        np.add.at(live, (rows // block_q, t_lo), 1)
        np.add.at(live, (rows // block_q, t_hi), -1)
    return int((np.cumsum(live, axis=1)[:, :num_kv] > 0).sum())


def _record_tiles(passes, bd, block_q, block_k, t_pad, s_pad, **mask):
    """The gauges of a call under a static mask, set while it is traced:
    whole and masked tiles a head for every such mask; under the
    block-diffusion mask also their sum, the tiles visited, beside the
    live ones."""
    from smdistributed_modelparallel_tpu.utils.telemetry import (
        record_flash_tiles,
    )

    counts = _tile_counts(block_q, block_k, t_pad, s_pad, bd, **mask)
    live = None
    if bd is not None:
        live = _bd_live_tiles(mask["q_len"] // 2, bd, block_q, block_k, t_pad)
    for name in passes:
        whole, masked = counts[name]
        record_flash_tiles(name, None if live is None else whole + masked,
                           live, whole=whole, masked=masked)


def _walk(ranges, body_of, init):
    """``body_of(masked)`` over each ``(lo, hi, masked)`` of ``ranges`` in
    turn, one carry."""
    for lo, hi, masked in ranges:
        init = jax.lax.fori_loop(lo, hi, body_of(masked), init)
    return init


def _split(bounds, whole, xp, lead=True, trail=True):
    """The range ``bounds`` of tiles as ``(lo, hi, masked)`` triples in
    walking order: the edge tiles before ``whole`` (clipped into the
    range), the whole tiles, the edge tiles after. ``lead`` / ``trail``
    false: the mask's shape leaves no edge tile on that side, so no loop
    is made for one (an empty ``whole`` then lies at that end). The one
    place that says a tile needs no mask."""
    lo, hi = bounds
    w_lo = xp.minimum(xp.maximum(whole[0], lo), hi)
    w_hi = xp.minimum(xp.maximum(whole[1], w_lo), hi)
    return ([(lo, w_lo, True)] if lead else []) + [(w_lo, w_hi, False)] + (
        [(w_hi, hi, True)] if trail else [])


def _kv_ranges(q_offset, block_q, num_kv, has_ids, bd, *, q_len, kv_len,
               causal, window, block_k, xp=jnp):
    """The ``(lo, hi, masked)`` ranges of kv tiles a program of the forward
    or the dq pass walks for its ``block_q`` rows from ``q_offset``, in
    order. Under the block-diffusion mask the noisy rows' own blocks, all
    edge tiles, then the clean prefix cut into its edge tiles and its whole
    ones; where global ids decide at run time every tile; else the one
    range the static mask leaves, every tile of it masked (the causal and
    band masks are a few compares a tile, which on the chip cost less than
    a second loop costs a program: PERF.md section 6, PR 45)."""
    q_hi = q_offset + block_q
    if bd is not None:
        half = q_len // 2
        own, prefix = _bd_kv_ranges(
            q_offset, q_hi, half=half, blk=bd, block_k=block_k, xp=xp)
        whole = _bd_kv_whole(
            q_offset, q_hi, half=half, blk=bd, block_k=block_k, xp=xp)
        return [(*own, True)] + _split(
            prefix, whole, xp, lead=half % block_k > 0)
    if has_ids:
        return [(0, num_kv, True)]
    return [(*_kv_bounds(
        q_offset, q_hi, q_len=q_len, kv_len=kv_len, causal=causal,
        window=window, block_k=block_k, num_kv=num_kv, xp=xp), True)]


def _q_ranges(k_offset, block_k, num_q, has_ids, bd, *, q_len, kv_len,
              causal, window, block_q, xp=jnp):
    """``_kv_ranges``' mirror: the ranges of q tiles a program of the dkv
    pass walks for its ``block_k`` keys from ``k_offset``."""
    k_hi = k_offset + block_k
    if bd is not None:
        half = q_len // 2
        noisy, clean = _bd_q_ranges(
            k_offset, k_hi, half=half, blk=bd, block_q=block_q, xp=xp)
        w_noisy, w_clean = _bd_q_whole(
            k_offset, k_hi, half=half, blk=bd, block_q=block_q, xp=xp)
        return _split(noisy, w_noisy, xp, trail=half % block_q > 0) + _split(
            clean, w_clean, xp, trail=q_len % block_q > 0)
    if has_ids:
        return [(0, num_q, True)]
    return [(*_q_bounds(
        k_offset, k_hi, q_len=q_len, kv_len=kv_len, causal=causal,
        window=window, block_q=block_q, num_q=num_q, xp=xp), True)]


def _tile_counts(block_q, block_k, t_pad, s_pad, bd, **mask):
    """``{pass: (whole, masked)}`` of one head's kernels under a static
    mask: the tiles its programs walk with no mask in the body and with
    one, summed over the programs from the ranges they walk."""
    def count(ranges, programs):
        out = [0, 0]
        for lo, hi, masked in ranges:
            out[masked] += int(np.broadcast_to(
                np.maximum(hi - lo, 0), programs).sum())
        return tuple(out)

    num_q, num_kv = t_pad // block_q, s_pad // block_k
    by_q = count(_kv_ranges(
        np.arange(num_q) * block_q, block_q, num_kv, False, bd,
        block_k=block_k, xp=np, **mask), num_q)
    by_kv = count(_q_ranges(
        np.arange(num_kv) * block_k, block_k, num_q, False, bd,
        block_q=block_q, xp=np, **mask), num_kv)
    return {"fwd": by_q, "dq": by_q, "dkv": by_kv}


_VMEM_CAP = 100 << 20          # of a v5e core's 128 MiB
_VMEM_TILES = 16 << 20         # room for the tiles: the default scoped limit


def _bd_vmem_bytes(length, hd, itemsize):
    """Scoped VMEM a call under the block-diffusion mask asks for: the
    whole K and V (or Q and dO) of a head over ``length`` positions sit in
    VMEM, twice for the pipeline's two buffers (16 MiB at 16,384
    positions of 128 in bfloat16, the whole of the default scoped limit),
    and the tiles beside them."""
    return 2 * (2 * length * hd * itemsize) + _VMEM_TILES


def bd_fits_vmem(length, hd, itemsize):
    """Whether a head's two-copy stream of ``length`` positions can be
    held (``ops/attention.py``'s gate for such a call)."""
    return _bd_vmem_bytes(length, -(-hd // 128) * 128, itemsize) <= _VMEM_CAP


def _bd_compiler_params(length, hd_pad, itemsize):
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=min(
            _bd_vmem_bytes(length, hd_pad, itemsize), _VMEM_CAP))}


def _whole_operand_params(length, hd_pad, hdv_pad, itemsize, block, tile,
                          bd, backward=False):
    """``pallas_call`` arguments for a call that holds two whole operands
    of a head over ``length`` positions (K and V, or Q and dO) in two
    buffers each. Under the block-diffusion mask always what the stream
    takes. Otherwise nothing, and the compiled call is what it was, while
    the operands and what the call holds beside them fit the default
    scoped limit: its blocks of ``block`` rows in two buffers, read in
    ``itemsize`` and written in up to float32, and a tile's (``tile``
    elements) float32 scores and probabilities, with their gradients in a
    ``backward`` call. That is an upper bound: the compiler's own count
    differs by the call and by the number of heads. Past it, the operands
    and the limit's worth of room: 8,192 positions of 128 in float32, the
    eager init pass of a model whose step runs in bfloat16, fill the limit
    with the operands alone."""
    if bd is not None:
        return _bd_compiler_params(length, hd_pad, itemsize)
    whole = 2 * length * (hd_pad + hdv_pad) * itemsize
    beside = (2 * block * (hd_pad + hdv_pad) * (itemsize + 4)
              + (4 if backward else 2) * tile * 4)
    if whole + beside <= _VMEM_TILES:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=min(whole + _VMEM_TILES, _VMEM_CAP))}


# ----------------------------------------------------------------------
# Forward
# ----------------------------------------------------------------------

def _ids_mask(rows_loc, cols_loc, rid, cid, *, q_len, kv_len, causal, window):
    """Mask for index-vector ("ids") mode: padding by LOCAL indices,
    causal/window by the GLOBAL ids carried in the q_ids/kv_ids inputs —
    this is what lets a kernel call over one ring-attention block pair
    apply the global causal relation (including zigzag-reordered rows)."""
    keep = (rows_loc < q_len) & (cols_loc < kv_len)
    if causal:
        keep &= cid <= rid
        if window is not None:
            keep &= rid - cid < window
    elif window is not None:
        keep &= jnp.abs(rid - cid) < window
    return keep


def _ids_rmax(qid_ref, q_offset, block_q, q_len):
    """Max global row id among this program's valid q rows (for causal
    block skipping)."""
    ids = qid_ref[0, pl.ds(q_offset, block_q)][None, :]
    loc = q_offset + jax.lax.broadcasted_iota(jnp.int32, (1, block_q), 1)
    return jnp.max(jnp.where(loc < q_len, ids, -1))


def _ids_cmin(kid_ref, k_offset, block_k, kv_len):
    """Min global col id among valid kv cols of a block (for skipping)."""
    ids = kid_ref[0, pl.ds(k_offset, block_k)][None, :]
    loc = k_offset + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    return jnp.min(jnp.where(loc < kv_len, ids, jnp.int32(2**30)))


def _tile_keep(masked, hashed, q_offset, k_offset, shape, q_ids, kv_ids,
               bd, *, q_len, kv_len, causal, window, keys_major=False):
    """``(keep, hrows, hcols)`` of the [block_q, block_k] tile at
    ``q_offset``, ``k_offset``: its mask, and the coordinates the dropout
    hash counts by (the global ids where the call carries them). ``keep``
    is None for a whole tile (``masked`` false), the coordinates unless
    ``hashed``: a whole tile of a call with no dropout builds neither.

    ``keys_major`` (the dkv pass): the tile is ``shape`` = [block_k,
    block_q], keys down axis 0 and rows along axis 1, and the ids come as
    they broadcast there, a [1, block_q] row and a [block_k, 1] column.
    Every mask and the hash are functions of the pair (row, key), so what
    is returned is the other orientation's transpose."""
    if not (masked or hashed):
        return None, None, None
    q_axis = int(keys_major)
    rows = q_offset + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    cols = k_offset + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    hrows, hcols = rows, cols
    if q_ids is not None and keys_major:
        hrows, hcols = q_ids, kv_ids
    elif q_ids is not None:
        hrows, hcols = q_ids[:, None], kv_ids[None, :]
    if not masked:
        return None, hrows, hcols
    if q_ids is not None:
        keep = _ids_mask(rows, cols, hrows, hcols, q_len=q_len,
                         kv_len=kv_len, causal=causal, window=window)
    elif bd is not None:
        keep = _bd_mask(q_offset, k_offset, shape[q_axis], shape[1 - q_axis],
                        half=q_len // 2, blk=bd, keys_major=keys_major)
    else:
        keep = _tile_mask(rows, cols, q_len=q_len, kv_len=kv_len,
                          causal=causal, window=window)
    return keep, hrows, hcols


def _bh_remap(b, h_local, head_total, head0_ref):
    """Flat (batch*local_head) program index -> GLOBAL batch*head id for
    the dropout hash. Identity when heads are unsharded; under Ulysses the
    local heads are a window [head0, head0+h_local) of the global heads."""
    if head0_ref is None:
        return b
    return (
        (b // h_local) * head_total + head0_ref[0, 0] + (b % h_local)
    )


def _fwd_kernel(*refs, scale, block_q, block_k, q_len, kv_len, causal,
                window, rate, has_kpm, has_seed, s_total, has_ids=False,
                h_local=None, head_total=None, has_head0=False, bd=None):
    it = iter(refs)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    kpm_ref = next(it) if has_kpm else None
    seed_ref = next(it) if has_seed else None
    head0_ref = next(it) if has_head0 else None
    qid_ref = next(it) if has_ids else None
    kid_ref = next(it) if has_ids else None
    o_ref, lse_ref = next(it), next(it)

    b = pl.program_id(0)
    i = pl.program_id(1)
    # MXU operands stay in their input dtype (bf16 on the training path):
    # the v5e MXU does bf16 x bf16 -> fp32-accumulate natively, while fp32
    # matmuls decompose into multiple passes. bf16 products accumulated in
    # fp32 are exact, so post-scaling the fp32 scores keeps score math fp32
    # (N8 parity) at native throughput.
    q = q_ref[0]                                      # [bq, hd]
    q_offset = i * block_q
    q_ids = None
    if has_ids:
        q_ids = qid_ref[0, pl.ds(q_offset, block_q)]
        r_max = _ids_rmax(qid_ref, q_offset, block_q, q_len)

    mask = dict(q_len=q_len, kv_len=kv_len, causal=causal, window=window)

    def compute(j, carry, masked):
        acc, m, l = carry
        k_blk = k_ref[0, pl.ds(j * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                              # [bq, bk]
        if scale != 1.0:
            s = s * scale
        if kpm_ref is not None:
            s = s + kpm_ref[0, pl.ds(j * block_k, block_k)][None, :]
        keep, hrows, hcols = _tile_keep(
            masked, rate > 0.0, q_offset, j * block_k, s.shape, q_ids,
            kid_ref[0, pl.ds(j * block_k, block_k)] if has_ids else None,
            bd, **mask)
        if masked:
            s = jnp.where(keep, s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        if rate > 0.0:
            bh = _bh_remap(b, h_local, head_total, head0_ref)
            dkeep = _dropout_keep(seed_ref[0, 0], bh, hrows, hcols,
                                  s_total, rate)
            p = jnp.where(dkeep, p, 0.0)
        acc_new = acc * alpha + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return acc_new, m_new, l_new

    def body_of(masked):
        if not (has_ids and causal):
            return functools.partial(compute, masked=masked)

        # Data-dependent block skip: the static _kv_bounds cannot see the
        # global ids, so each kv block is skipped at runtime when its
        # minimum col id exceeds every row id in this q block.
        def body(j, carry):
            visible = _ids_cmin(kid_ref, j * block_k, block_k, kv_len) <= r_max
            return jax.lax.cond(
                visible, lambda c: compute(j, c, masked), lambda c: c, carry
            )

        return body

    ranges = _kv_ranges(
        q_offset, block_q, k_ref.shape[1] // block_k, has_ids, bd,
        block_k=block_k, **mask)
    acc0 = jnp.zeros((block_q, v_ref.shape[-1]), jnp.float32)
    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc, m, l = _walk(ranges, body_of, (acc0, m0, l0))
    inv_keep = 1.0 / (1.0 - rate) if rate > 0.0 else 1.0
    o_ref[0] = (acc * inv_keep / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    lse = jnp.where(
        l[:, 0] > 0.0, m[:, 0] + jnp.log(jnp.maximum(l[:, 0], 1e-30)),
        _LSE_MASKED,
    )
    lse_ref[0] = lse[None, :]


# ----------------------------------------------------------------------
# Backward
# ----------------------------------------------------------------------

def _bwd_dq_kernel(*refs, scale, block_q, block_k, q_len, kv_len, causal,
                   window, rate, has_kpm, has_seed, s_total, has_ids=False,
                   h_local=None, head_total=None, has_head0=False, bd=None):
    it = iter(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = (next(it) for _ in range(6))
    kpm_ref = next(it) if has_kpm else None
    seed_ref = next(it) if has_seed else None
    head0_ref = next(it) if has_head0 else None
    qid_ref = next(it) if has_ids else None
    kid_ref = next(it) if has_ids else None
    dq_ref = next(it)

    b = pl.program_id(0)
    i = pl.program_id(1)
    q = q_ref[0]                                      # [bq, hd] input dtype
    do = do_ref[0]
    lse = lse_ref[0, 0, :][:, None]                   # [bq, 1]
    delta = delta_ref[0, 0, :][:, None]
    q_offset = i * block_q
    inv_keep = 1.0 / (1.0 - rate) if rate > 0.0 else 1.0
    q_ids = None
    if has_ids:
        q_ids = qid_ref[0, pl.ds(q_offset, block_q)]
        r_max = _ids_rmax(qid_ref, q_offset, block_q, q_len)

    mask = dict(q_len=q_len, kv_len=kv_len, causal=causal, window=window)

    def compute(j, dq_acc, masked):
        k_blk = k_ref[0, pl.ds(j * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if scale != 1.0:
            s = s * scale
        if kpm_ref is not None:
            s = s + kpm_ref[0, pl.ds(j * block_k, block_k)][None, :]
        keep, hrows, hcols = _tile_keep(
            masked, rate > 0.0, q_offset, j * block_k, s.shape, q_ids,
            kid_ref[0, pl.ds(j * block_k, block_k)] if has_ids else None,
            bd, **mask)
        p = jnp.exp(s - lse)                          # [bq, bk]
        if masked:
            p = jnp.where(keep, p, 0.0)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if rate > 0.0:
            bh = _bh_remap(b, h_local, head_total, head0_ref)
            dkeep = _dropout_keep(seed_ref[0, 0], bh, hrows, hcols,
                                  s_total, rate)
            dp = jnp.where(dkeep, dp * inv_keep, 0.0)
        ds = p * (dp - delta) * scale                 # d(q.k^T)
        return dq_acc + jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def body_of(masked):
        if not (has_ids and causal):
            return functools.partial(compute, masked=masked)

        def body(j, dq_acc):
            visible = _ids_cmin(kid_ref, j * block_k, block_k, kv_len) <= r_max
            return jax.lax.cond(
                visible, lambda c: compute(j, c, masked), lambda c: c, dq_acc
            )

        return body

    ranges = _kv_ranges(
        q_offset, block_q, k_ref.shape[1] // block_k, has_ids, bd,
        block_k=block_k, **mask)
    dq = _walk(ranges, body_of, jnp.zeros((block_q, q.shape[-1]), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, block_q, block_k, q_len, kv_len, causal,
                    window, rate, has_kpm, has_seed, s_total, has_ids=False,
                    h_local=None, head_total=None, has_head0=False, bd=None):
    it = iter(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = (next(it) for _ in range(6))
    kpm_ref = next(it) if has_kpm else None
    seed_ref = next(it) if has_seed else None
    head0_ref = next(it) if has_head0 else None
    qid_ref = next(it) if has_ids else None
    kid_ref = next(it) if has_ids else None
    dk_ref, dv_ref = next(it), next(it)

    b = pl.program_id(0)
    j = pl.program_id(1)
    k_blk = k_ref[0]                                  # [bk, hd] input dtype
    v_blk = v_ref[0]
    k_offset = j * block_k
    inv_keep = 1.0 / (1.0 - rate) if rate > 0.0 else 1.0
    # The tile is keys-major, [bk, bq]: what belongs to the program's own
    # keys (its slice of the bias, its global ids) is turned into a
    # [bk, 1] column here, once a program and not once a tile.
    kpm_col = None
    if kpm_ref is not None:
        kpm_col = kpm_ref[0, pl.ds(k_offset, block_k)][:, None]
    kv_ids = None
    if has_ids:
        kv_ids = kid_ref[0, pl.ds(k_offset, block_k)][:, None]
        c_min = _ids_cmin(kid_ref, k_offset, block_k, kv_len)

    mask = dict(q_len=q_len, kv_len=kv_len, causal=causal, window=window)

    def compute(i, carry, masked):
        dk_acc, dv_acc = carry
        q_blk = q_ref[0, pl.ds(i * block_q, block_q), :]
        do_blk = do_ref[0, pl.ds(i * block_q, block_q), :]
        lse = lse_ref[0, :, pl.ds(i * block_q, block_q)]      # [1, bq]
        delta = delta_ref[0, :, pl.ds(i * block_q, block_q)]
        st = jax.lax.dot_general(
            k_blk, q_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                              # s^T, [bk, bq]
        if scale != 1.0:
            st = st * scale
        if kpm_col is not None:
            st = st + kpm_col
        keep, hrows, hcols = _tile_keep(
            masked, rate > 0.0, i * block_q, k_offset, st.shape,
            qid_ref[:, pl.ds(i * block_q, block_q)] if has_ids else None,
            kv_ids, bd, keys_major=True, **mask)
        pt = jnp.exp(st - lse)
        if masked:
            pt = jnp.where(keep, pt, 0.0)
        dpt = jax.lax.dot_general(
            v_blk, do_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                              # dp^T = v.dO^T
        if rate > 0.0:
            bh = _bh_remap(b, h_local, head_total, head0_ref)
            dkeep = _dropout_keep(seed_ref[0, 0], bh, hrows, hcols,
                                  s_total, rate)
            pt_drop = jnp.where(dkeep, pt * inv_keep, 0.0)
            dpt = jnp.where(dkeep, dpt * inv_keep, 0.0)
        else:
            pt_drop = pt
        dv_acc = dv_acc + jax.lax.dot_general(
            pt_drop.astype(do_blk.dtype), do_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                              # [bk, hd_v]
        dst = pt * (dpt - delta) * scale               # d(k.q^T)
        dk_acc = dk_acc + jax.lax.dot_general(
            dst.astype(q_blk.dtype), q_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dk_acc, dv_acc

    def body_of(masked):
        if not (has_ids and causal):
            return functools.partial(compute, masked=masked)

        def body(i, carry):
            visible = c_min <= _ids_rmax(qid_ref, i * block_q, block_q, q_len)
            return jax.lax.cond(
                visible, lambda c: compute(i, c, masked), lambda c: c, carry
            )

        return body

    ranges = _q_ranges(
        k_offset, block_k, q_ref.shape[1] // block_q, has_ids, bd,
        block_q=block_q, **mask)
    z = jnp.zeros((block_k, k_blk.shape[-1]), jnp.float32)
    zv = (z if v_blk.shape[-1] == k_blk.shape[-1]
          else jnp.zeros((block_k, v_blk.shape[-1]), jnp.float32))
    dk, dv = _walk(ranges, body_of, (z, zv))
    # ds^T carries exactly one *scale factor and q_blk is raw (unscaled),
    # so dk = ds^T.q is already correct.
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# ----------------------------------------------------------------------
# Host-side wrappers
# ----------------------------------------------------------------------

def resolve_blocks(block_q, block_k, default_q=256, default_k=512):
    """Block-size resolution, the ONE source of truth for every entry
    point: an explicit argument wins, else the smp config override
    (``pallas_attn_block_{q,k}``), else the per-path default."""
    from smdistributed_modelparallel_tpu.backend.state import state

    cfg = state.cfg
    if block_q is None:
        block_q = (
            getattr(cfg, "pallas_attn_block_q", None) if cfg is not None
            else None
        ) or default_q
    if block_k is None:
        block_k = (
            getattr(cfg, "pallas_attn_block_k", None) if cfg is not None
            else None
        ) or default_k
    return block_q, block_k


def _clamp_block(block, dim):
    """Clamp a block size to a sequence dim, keeping lane alignment: the
    result is min(block, dim rounded up to 128), so a short/ragged dim
    yields ONE aligned block (padded by ``_prep``) instead of a raw
    ``min`` that would hand Mosaic an unaligned (non-multiple-of-128)
    block shape for dims like 300."""
    return min(block, ((dim + 127) // 128) * 128)


def _kv_index(group):
    """Index maps of a K/V block: the whole [s_pad] rows for the forward
    and dq kernels, one kv block for the dkv kernel. Program ``b`` runs
    query head ``b % H`` of batch row ``b // H``; with H = H_kv * group its
    KV head sits at ``b // group`` of the [B*H_kv] leading axis."""
    if group == 1:
        return (lambda b, i: (b, 0, 0)), (lambda b, j: (b, j, 0))
    return (lambda b, i: (b // group, 0, 0)), (lambda b, j: (b // group, j, 0))


def _pad_width(hd):
    """The lane width a head size runs at: itself in whole 128s, else the
    next power of two from 128 up."""
    return max(128, int(2 ** np.ceil(np.log2(hd)))) if hd % 128 else hd


def _prep(q, k, v, block_q, block_k):
    """``(qt, kt, vt, (B, T, S, H, hd, hd_pad, t_pad, s_pad, hdv,
    hdv_pad))``: the operands as [B*heads, positions, width] padded to
    whole blocks and lanes, q and k at the keys' width, v at its own."""
    B, T, H, hd = q.shape
    S, hdv = k.shape[1], v.shape[-1]
    if H % k.shape[2] or k.shape[2] != v.shape[2] or k.shape[-1] != hd:
        raise ValueError(
            f"flash attention: {H} query heads of {hd} against "
            f"{k.shape[2]} key heads of {k.shape[-1]} and {v.shape[2]} value "
            "heads (H must be a multiple of H_kv, q and k of one size)."
        )

    def to_bht(x):
        return x.transpose(0, 2, 1, 3).reshape(
            B * x.shape[2], x.shape[1], x.shape[3])

    qt, kt, vt = to_bht(q), to_bht(k), to_bht(v)
    hd_pad, hdv_pad = _pad_width(hd), _pad_width(hdv)
    t_pad = ((T + block_q - 1) // block_q) * block_q
    s_pad = ((S + block_k - 1) // block_k) * block_k
    if hd_pad != hd or t_pad != T:
        qt = jnp.pad(qt, ((0, 0), (0, t_pad - T), (0, hd_pad - hd)))
    if hd_pad != hd or s_pad != S:
        kt = jnp.pad(kt, ((0, 0), (0, s_pad - S), (0, hd_pad - hd)))
    if hdv_pad != hdv or s_pad != S:
        vt = jnp.pad(vt, ((0, 0), (0, s_pad - S), (0, hdv_pad - hdv)))
    return qt, kt, vt, (B, T, S, H, hd, hd_pad, t_pad, s_pad, hdv, hdv_pad)


def _group_of(q, k):
    return q.shape[2] // k.shape[2]


def _common_inputs(kpad_bias, seed, s_pad, B, H, interpret, head0=None):
    """(extra_inputs, extra_specs, has_kpm, has_seed, has_head0) shared by
    all kernels."""
    inputs, specs = [], []
    has_kpm = kpad_bias is not None
    if has_kpm:
        S = kpad_bias.shape[1]
        kpm = kpad_bias.astype(jnp.float32)
        if s_pad != S:
            kpm = jnp.pad(kpm, ((0, 0), (0, s_pad - S)), constant_values=NEG_INF)
        if kpm.shape[0] != B:
            # Broadcast batch dim: the index_map below computes b // H and
            # must never address past the array's blocks.
            kpm = jnp.broadcast_to(kpm, (B, s_pad))
        inputs.append(kpm)
        specs.append(pl.BlockSpec((1, s_pad), lambda b, i: (b // H, 0)))

    def scalar_spec():
        return pl.BlockSpec(
            (1, 1), lambda b, i: (0, 0),
            memory_space=pltpu.SMEM if not interpret else None,
        )

    has_seed = seed is not None
    if has_seed:
        inputs.append(seed.reshape(1, 1).astype(jnp.int32))
        specs.append(scalar_spec())
    has_head0 = head0 is not None
    if has_head0:
        inputs.append(jnp.asarray(head0).reshape(1, 1).astype(jnp.int32))
        specs.append(scalar_spec())
    return inputs, specs, has_kpm, has_seed, has_head0


def _ids_extra(q_ids, kv_ids, t_pad, s_pad):
    """(inputs, specs) for index-vector mode: [1, t_pad]/[1, s_pad] int32
    global row/col id arrays, broadcast to every program."""
    qi = jnp.pad(q_ids.astype(jnp.int32), (0, t_pad - q_ids.shape[0]))
    ki = jnp.pad(kv_ids.astype(jnp.int32), (0, s_pad - kv_ids.shape[0]))
    return (
        [qi[None, :], ki[None, :]],
        [
            pl.BlockSpec((1, t_pad), lambda b, i: (0, 0)),
            pl.BlockSpec((1, s_pad), lambda b, i: (0, 0)),
        ],
    )


def _flash_fwd_impl(q, k, v, kpad_bias, seed, scale, causal, window,
                    dropout_rate, block_q, block_k, interpret,
                    q_ids=None, kv_ids=None, head0=None, head_total=None,
                    counter_len=None, bd=None):
    qt, kt, vt, (B, T, S, H, hd, hd_pad, t_pad, s_pad, hdv, hdv_pad) = _prep(
        q, k, v, block_q, block_k
    )
    from smdistributed_modelparallel_tpu.utils.telemetry import (
        record_flash_v_head_dim,
    )

    record_flash_v_head_dim(hdv)
    extra, extra_specs, has_kpm, has_seed, has_head0 = _common_inputs(
        kpad_bias, seed, s_pad, B, H, interpret, head0
    )
    has_ids = q_ids is not None
    if has_ids:
        id_in, id_specs = _ids_extra(q_ids, kv_ids, t_pad, s_pad)
        extra, extra_specs = extra + id_in, extra_specs + id_specs
    more = {}
    if not has_ids:
        _record_tiles(("fwd",), bd, block_q, block_k, t_pad, s_pad, q_len=T,
                      kv_len=S, causal=causal, window=window)
    if bd is not None:
        more = {"bd": bd}
    more_call = _whole_operand_params(
        s_pad, hd_pad, hdv_pad, qt.dtype.itemsize, block_q,
        block_q * block_k, bd)
    grid = (B * H, t_pad // block_q)
    kv_whole, _ = _kv_index(_group_of(q, k))
    kern = functools.partial(
        _fwd_kernel, scale=scale, block_q=block_q, block_k=block_k,
        q_len=T, kv_len=S, causal=causal, window=window,
        rate=dropout_rate if has_seed else 0.0,
        has_kpm=has_kpm, has_seed=has_seed,
        s_total=counter_len if counter_len is not None else s_pad,
        has_ids=has_ids, h_local=H, head_total=head_total or H,
        has_head0=has_head0, **more,
    )
    out, lse = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd_pad), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, s_pad, hd_pad), kv_whole),
            pl.BlockSpec((1, s_pad, hdv_pad), kv_whole),
            *extra_specs,
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, hdv_pad), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            # ids mode feeds the ring's fp32 online-softmax merge: per-step
            # partials must not round-trip through bf16 before accumulating.
            jax.ShapeDtypeStruct(
                (B * H, t_pad, hdv_pad),
                jnp.float32 if has_ids else q.dtype,
            ),
            jax.ShapeDtypeStruct((B * H, 1, t_pad), jnp.float32),
        ],
        name="smp_flash_fwd",
        interpret=interpret or FORCE_INTERPRET,
        **more_call,
    )(qt, kt, vt, *extra)
    o = out[:, :T, :hdv].reshape(B, H, T, hdv).transpose(0, 2, 1, 3)
    return o, lse


def _flash_bwd_impl(q, k, v, o, g, lse, kpad_bias, seed, scale, causal,
                    window, dropout_rate, block_q, block_k, interpret,
                    q_ids=None, kv_ids=None, head0=None, head_total=None,
                    counter_len=None, bd=None):
    qt, kt, vt, (B, T, S, H, hd, hd_pad, t_pad, s_pad, hdv, hdv_pad) = _prep(
        q, k, v, block_q, block_k
    )
    gt = g.transpose(0, 2, 1, 3).reshape(B * H, T, hdv)
    if hdv_pad != hdv or t_pad != T:
        gt = jnp.pad(gt, ((0, 0), (0, t_pad - T), (0, hdv_pad - hdv)))
    # delta = rowsum(dO * O): one fused elementwise+reduce pass in XLA.
    delta = jnp.sum(
        g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    ).transpose(0, 2, 1).reshape(B * H, 1, T)
    if t_pad != T:
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, t_pad - T)))

    extra, extra_specs, has_kpm, has_seed, has_head0 = _common_inputs(
        kpad_bias, seed, s_pad, B, H, interpret, head0
    )
    has_ids = q_ids is not None
    if has_ids:
        id_in, id_specs = _ids_extra(q_ids, kv_ids, t_pad, s_pad)
        extra, extra_specs = extra + id_in, extra_specs + id_specs
    common = dict(
        scale=scale, block_q=block_q, block_k=block_k, q_len=T, kv_len=S,
        causal=causal, window=window,
        rate=dropout_rate if has_seed else 0.0,
        has_kpm=has_kpm, has_seed=has_seed,
        s_total=counter_len if counter_len is not None else s_pad,
        has_ids=has_ids, h_local=H, head_total=head_total or H,
        has_head0=has_head0,
    )
    if not has_ids:
        _record_tiles(("dq", "dkv"), bd, block_q, block_k, t_pad, s_pad,
                      q_len=T, kv_len=S, causal=causal, window=window)
    if bd is not None:
        common["bd"] = bd
    held = functools.partial(
        _whole_operand_params, hd_pad=hd_pad, hdv_pad=hdv_pad,
        itemsize=qt.dtype.itemsize, tile=block_q * block_k, bd=bd,
        backward=True)
    res_spec_q = pl.BlockSpec((1, t_pad, hd_pad), lambda b, i: (b, 0, 0))
    res_spec_do = pl.BlockSpec((1, t_pad, hdv_pad), lambda b, i: (b, 0, 0))
    row_spec = pl.BlockSpec((1, 1, t_pad), lambda b, i: (b, 0, 0))
    group = _group_of(q, k)
    kv_whole, kv_block = _kv_index(group)
    # A query head's dk/dv is a partial sum when its KV head is shared:
    # fp32 out of the kernel, summed over the group below.
    partial_kv = group > 1

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid=(B * H, t_pad // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, hd_pad), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, s_pad, hd_pad), kv_whole),
            pl.BlockSpec((1, s_pad, hdv_pad), kv_whole),
            pl.BlockSpec((1, block_q, hdv_pad), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
            *extra_specs,
        ],
        out_specs=pl.BlockSpec((1, block_q, hd_pad), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (B * H, t_pad, hd_pad), jnp.float32 if has_ids else q.dtype
        ),
        name="smp_flash_bwd_dq",
        interpret=interpret or FORCE_INTERPRET,
        **held(max(s_pad, t_pad) if bd else s_pad, block=block_q),
    )(qt, kt, vt, gt, lse, delta, *extra)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common),
        grid=(B * H, s_pad // block_k),
        in_specs=[
            res_spec_q,
            pl.BlockSpec((1, block_k, hd_pad), kv_block),
            pl.BlockSpec((1, block_k, hdv_pad), kv_block),
            res_spec_do,
            row_spec,
            row_spec,
            *extra_specs,
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, hd_pad), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hdv_pad), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            # ids mode: fp32 per-step gradients for the ring's rotating
            # accumulators (see fwd out_shape note).
            jax.ShapeDtypeStruct(
                (B * H, s_pad, hd_pad),
                jnp.float32 if has_ids or partial_kv else k.dtype,
            ),
            jax.ShapeDtypeStruct(
                (B * H, s_pad, hdv_pad),
                jnp.float32 if has_ids or partial_kv else v.dtype,
            ),
        ],
        name="smp_flash_bwd_dkv",
        interpret=interpret or FORCE_INTERPRET,
        **held(max(s_pad, t_pad) if bd else t_pad, block=block_k),
    )(qt, kt, vt, gt, lse, delta, *extra)

    def from_bht(x, L, hd=hd):
        return x[:, :L, :hd].reshape(B, H, L, hd).transpose(0, 2, 1, 3)

    def kv_from_bht(x, like, hd=hd):
        if not partial_kv:
            return from_bht(x, S, hd)
        x = x[:, :S, :hd].reshape(B, H // group, group, S, hd).sum(axis=2)
        return x.astype(jnp.float32 if has_ids else like.dtype).transpose(
            0, 2, 1, 3)

    return (from_bht(dq, T), kv_from_bht(dk, k), kv_from_bht(dv, v, hdv))


# ----------------------------------------------------------------------
# custom_vjp surface
# ----------------------------------------------------------------------

@functools.partial(
    jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11, 12, 13, 14, 15)
)
def flash_attention(q, k, v, kpad_bias=None, seed=None, head0=None,
                    scale=None, causal=True, window=None, dropout_rate=0.0,
                    block_q=None, block_k=None, interpret=False,
                    head_total=None, counter_len=None, block_diffusion=None):
    """Flash attention over [B, T, H, hd] q and [B, S, H_kv, hd] k/v
    (H a multiple of H_kv; query head h reads KV head h // (H / H_kv)).

    ``kpad_bias``: additive float [B, S] bias (0 keep / -1e30 drop for
    boolean masks). ``seed``: int32 scalar array enabling dropout at
    ``dropout_rate``. ``head0``/``head_total``/``counter_len``: GLOBAL
    dropout-hash coordinates for head-sharded callers (Ulysses) — the
    local heads hash as window [head0, head0+H) of ``head_total`` global
    heads, with ``counter_len`` as the row-stride (defaults reproduce the
    local hash, bh = flat program index, stride = padded S). Fully-masked
    rows produce an undefined (zero-ish) output, matching
    softmax-of-all-masked degeneracy in the jnp path.

    ``block_diffusion``: a block length B puts the block-diffusion mask
    of a two-copy stream in place of ``causal`` and ``window`` (q, k and
    v hold 2L positions, [noisy ; clean], B dividing L): see the header.
    """
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    causal, window = _bd_checked(q, k, causal, window, block_diffusion)
    block_q, block_k = resolve_blocks(block_q, block_k)
    block_q = _clamp_block(block_q, q.shape[1])
    block_k = _clamp_block(block_k, k.shape[1])
    o, _ = _flash_fwd_impl(q, k, v, kpad_bias, seed, scale, causal, window,
                           dropout_rate, block_q, block_k, interpret,
                           head0=head0, head_total=head_total,
                           counter_len=counter_len, bd=block_diffusion)
    return o


def _bd_checked(q, k, causal, window, block_diffusion):
    """``(causal, window)`` as the kernels (and ``attention_core``'s jnp
    path) take them: under the block-diffusion mask neither applies (the
    mask is the whole relation), and the stream must be two copies of
    whole blocks."""
    if block_diffusion is None:
        return causal, window
    T = q.shape[1]
    if k.shape[1] != T or T % (2 * block_diffusion) or window is not None:
        raise ValueError(
            f"block-diffusion attention: q and k of {T} and {k.shape[1]} "
            f"positions, block length {block_diffusion}, window {window}: "
            "wants one stream of two copies of whole blocks and no window."
        )
    return False, None


def _fa_fwd(q, k, v, kpad_bias, seed, head0, scale, causal, window,
            dropout_rate, block_q, block_k, interpret, head_total,
            counter_len, block_diffusion):
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    causal, window = _bd_checked(q, k, causal, window, block_diffusion)
    block_q, block_k = resolve_blocks(block_q, block_k)
    block_q = _clamp_block(block_q, q.shape[1])
    block_k = _clamp_block(block_k, k.shape[1])
    o, lse = _flash_fwd_impl(q, k, v, kpad_bias, seed, scale, causal, window,
                             dropout_rate, block_q, block_k, interpret,
                             head0=head0, head_total=head_total,
                             counter_len=counter_len, bd=block_diffusion)
    # The two values a checkpointed layer keeps (``remat_policy``), so its
    # backward pass does not run the forward kernel again for them.
    o = checkpoint_name(o, FLASH_OUT_NAME)
    lse = checkpoint_name(lse, FLASH_LSE_NAME)
    return o, (q, k, v, o, lse, kpad_bias, seed, head0)


def _fa_bwd(scale, causal, window, dropout_rate, block_q, block_k, interpret,
            head_total, counter_len, block_diffusion, res, g):
    q, k, v, o, lse, kpad_bias, seed, head0 = res
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    causal, window = _bd_checked(q, k, causal, window, block_diffusion)
    block_q, block_k = resolve_blocks(block_q, block_k)
    block_q = _clamp_block(block_q, q.shape[1])
    block_k = _clamp_block(block_k, k.shape[1])
    dq, dk, dv = _flash_bwd_impl(
        q, k, v, o, g, lse, kpad_bias, seed, scale, causal, window,
        dropout_rate, block_q, block_k, interpret,
        head0=head0, head_total=head_total, counter_len=counter_len,
        bd=block_diffusion,
    )
    return dq, dk, dv, None, None, None


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ----------------------------------------------------------------------
# Index-vector ("ids") entry points — building blocks for ring attention
# ----------------------------------------------------------------------
#
# These are NOT custom_vjp surfaces: the ring-attention executor
# (ops/context_parallel.py) owns the differentiation, calling the forward
# per KV ring step (merging partials with the online-softmax rule) and the
# backward per step with the GLOBAL logsumexp — the standard blockwise
# flash decomposition distributed over the cp ring. q_ids / kv_ids carry
# the global sequence positions of the local blocks, which is what makes
# causal masking correct under the zigzag re-layout (non-contiguous rows).
# Dropout is not supported in ids mode (the ring falls back to the jnp
# path when attention dropout is active).


def _lse_to_rows(lse_raw, B, H, T):
    """Kernel-layout lse [B*H, 1, t_pad] -> [B, H, T]."""
    return lse_raw[:, 0, :T].reshape(B, H, T)


def _rows_to_lse(lse, t_pad):
    """[B, H, T] -> kernel layout [B*H, 1, t_pad] (padded with the masked
    sentinel so padded rows contribute p == 0 in the backward)."""
    B, H, T = lse.shape
    out = lse.reshape(B * H, 1, T)
    if t_pad != T:
        out = jnp.pad(out, ((0, 0), (0, 0), (0, t_pad - T)),
                      constant_values=_LSE_MASKED)
    return out


def flash_fwd_with_ids(q, k, v, kpad_bias, q_ids, kv_ids, *, scale, causal,
                       seed=None, dropout_rate=0.0, counter_len=None,
                       block_q=None, block_k=None, interpret=False,
                       head0=None, head_total=None):
    """One blockwise forward over a (q block, kv block) pair.

    Dropout hashes on the GLOBAL ids (rows/cols from q_ids/kv_ids, stride
    ``counter_len``; ``head0``/``head_total`` remap head-sharded callers'
    local heads to global ids, as in ``flash_attention``) so the pattern
    matches the jnp ring/Ulysses bodies bit for bit. Returns (o
    [B, T, H, hd] fp32-normalized per-block output, lse [B, H, T] with
    +_LSE_MASKED sentinel on fully-masked rows).
    """
    block_q, block_k = resolve_blocks(block_q, block_k, default_k=256)
    block_q = _clamp_block(block_q, q.shape[1])
    block_k = _clamp_block(block_k, k.shape[1])
    o, lse = _flash_fwd_impl(
        q, k, v, kpad_bias, seed, scale, causal, None, dropout_rate,
        block_q, block_k, interpret, q_ids=q_ids, kv_ids=kv_ids,
        counter_len=counter_len, head0=head0, head_total=head_total,
    )
    B, T, H = q.shape[0], q.shape[1], q.shape[2]
    return o, _lse_to_rows(lse, B, H, T)


def flash_bwd_with_ids(q, k, v, o, g, lse, kpad_bias, q_ids, kv_ids, *,
                       scale, causal, seed=None, dropout_rate=0.0,
                       counter_len=None, block_q=None, block_k=None,
                       interpret=False, head0=None, head_total=None):
    """Blockwise backward for one (q block, kv block) pair given the GLOBAL
    per-row logsumexp ``lse`` [B, H, T] (+_LSE_MASKED sentinel rows) and
    the GLOBAL output ``o`` / cotangent ``g``. Returns (dq, dk, dv)."""
    block_q, block_k = resolve_blocks(block_q, block_k, default_k=256)
    block_q = _clamp_block(block_q, q.shape[1])
    block_k = _clamp_block(block_k, k.shape[1])
    t_pad = ((q.shape[1] + block_q - 1) // block_q) * block_q
    lse_raw = _rows_to_lse(lse, t_pad)
    return _flash_bwd_impl(
        q, k, v, o, g, lse_raw, kpad_bias, seed, scale, causal, None,
        dropout_rate, block_q, block_k, interpret, q_ids=q_ids,
        kv_ids=kv_ids, counter_len=counter_len, head0=head0,
        head_total=head_total,
    )
