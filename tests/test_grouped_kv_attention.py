"""Grouped KV heads in the flash kernels (interpret mode on the CPU) and in
the dispatcher's jnp path: groups of 6 and 9 query heads against one KV
head, window 512 and none, values and gradients, against a naive softmax
over repeated K/V; and that a multi-head call lowers to what it lowered to
before the kernels took groups."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from smdistributed_modelparallel_tpu.ops.attention import attention_core
from smdistributed_modelparallel_tpu.ops.pallas_attention import (
    flash_attention,
)


def _naive(q, k, v, window=None):
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    T = q.shape[1]
    s = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(q.shape[-1])
    rows, cols = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    keep = cols <= rows
    if window is not None:
        keep &= rows - cols < window
    s = jnp.where(keep[None, None], s, -1e30)
    return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v)


def _qkv(seed, B, T, H, Hkv, hd):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (B, T, H, hd)),
            jax.random.normal(ks[1], (B, T, Hkv, hd)),
            jax.random.normal(ks[2], (B, T, Hkv, hd)),
            jax.random.normal(ks[3], (B, T, H, hd)))


def _flash(q, k, v, window=None, bq=128, bk=128):
    return flash_attention(q, k, v, None, None, None, None, True, window,
                           0.0, bq, bk, True)


@pytest.mark.parametrize("heads,window", [(6, None), (9, 512), (9, 100)],
                         ids=["full_6to1", "window512_9to1", "window100_9to1"])
def test_flash_grouped_values_and_gradients(heads, window):
    # T = 640 > window 512: two of the five 128-row blocks lie wholly
    # outside some queries' band.
    q, k, v, w = _qkv(3, 1, 640, heads, 1, 32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, window) * w)

    np.testing.assert_allclose(
        np.asarray(_flash(q, k, v, window)),
        np.asarray(_naive(q, k, v, window)), atol=3e-5)
    got = jax.grad(loss(_flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(_naive), argnums=(0, 1, 2))(q, k, v)
    assert got[1].shape == k.shape and got[2].shape == v.shape
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_flash_two_kv_heads_two_rows():
    """Batch 2 x 2 KV heads x groups of 3: the index map picks the KV head
    of the right batch row."""
    q, k, v, _ = _qkv(5, 2, 256, 6, 2, 32)
    np.testing.assert_allclose(
        np.asarray(_flash(q, k, v)), np.asarray(_naive(q, k, v)), atol=3e-5)


def test_attention_core_jnp_path_takes_groups():
    q, k, v, _ = _qkv(7, 2, 64, 6, 2, 16)
    got = attention_core(q, k, v, causal=True, window=16, use_pallas=False)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_naive(q, k, v, 16)), atol=3e-5)


def test_heads_must_divide():
    q, k, v, _ = _qkv(1, 1, 128, 6, 4, 32)
    with pytest.raises(ValueError, match="multiple of H_kv"):
        _flash(q, k, v)


# sha256 of the jaxpr (source locations stripped) that `jax.grad` of a
# multi-head flash_attention call (B 2, T 256, H 4, hd 64, bf16, causal,
# window 128, not interpreted, under the matmul precision conftest.py
# pins) traced to on the parent commit of the PR that
# added KV groups (5a0602f): the three pallas_calls with their grids, block
# shapes, index maps, output types and kernel bodies are in that text.
# Since PR 40 with two ``name`` equations more, on the forward kernel's
# output and logsumexp (``parallel/memory.remat_policy`` keeps the two),
# and the variables after them renamed: nothing else differs from that
# text (381a496c...1d81), equation for equation. Since PR 45 (one
# ``_tile_keep`` for the three kernels' mask lines) the forward and the dq
# body multiply the tile index by ``block_k`` before the rows' iota, not
# after it, and the variables in between are renamed: no other line
# differs from PR 40's text (6d521284...cb19). Since PR 50 the dkv pass's
# tile body is keys-major (``lse`` and ``delta`` read as [1, 128] rows,
# ``k q^T`` and ``v dO^T``, the iotas' dimensions swapped, the dv and dk
# products contracting their left operand's dimension 1) and the variables
# after it are renamed: every line before that body, the forward and the
# dq kernel among them, is PR 45's (9f0f0b3e...93fe).
_MHA_JAXPR_SHA256 = (
    "21e3b7608819a16c9d824213c80c4f8430356469f2ba57d2e606adb0afc61993")


def test_multi_head_traces_as_before():
    shape = jax.ShapeDtypeStruct((2, 256, 4, 64), jnp.bfloat16)

    def loss(q, k, v):
        o = flash_attention(q, k, v, None, None, None, None, True, 128, 0.0,
                            128, 128, False)
        return jnp.sum(o.astype(jnp.float32))

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(
        shape, shape, shape))
    text = re.sub(r" at [^\s,)]*:\d+", "", text)
    assert hashlib.sha256(text.encode()).hexdigest() == _MHA_JAXPR_SHA256
