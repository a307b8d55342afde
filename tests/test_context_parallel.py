"""M6 tests: ring attention + Ulysses context parallelism.

New capability vs the reference (SURVEY §5.7); tested like the TP tiers:
parity of the cp-sharded computation against the unsharded one on the
8-device CPU mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import smdistributed_modelparallel_tpu as smp
from smdistributed_modelparallel_tpu.backend.state import state
from smdistributed_modelparallel_tpu.nn.cross_entropy import (
    vocab_parallel_cross_entropy,
)
from smdistributed_modelparallel_tpu.nn.transformer import (
    DistributedTransformerLMHead,
)


def _naive(q, k, v, causal=True, kp=None):
    hd = q.shape[-1]
    scale = 1.0 / np.sqrt(hd)
    T = q.shape[1]
    s = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32) * scale
    if kp is not None:
        s = s + kp[:, None, None, :]
    if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p, v.astype(jnp.float32)).astype(q.dtype)


class TestCpAttentionParity:
    @pytest.mark.parametrize("impl", ["ring", "ulysses"])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_full_attention(self, impl, causal):
        smp.shutdown()
        smp.init({
            "context_parallel_degree": 4, "ddp": True,
            "context_parallel_impl": impl,
        })
        from smdistributed_modelparallel_tpu.ops.context_parallel import (
            cp_attention,
        )

        B, T, H, hd = 2, 32, 4, 8
        ks = jax.random.split(jax.random.key(0), 3)
        q = jax.random.normal(ks[0], (B, T, H, hd))
        k = jax.random.normal(ks[1], (B, T, H, hd))
        v = jax.random.normal(ks[2], (B, T, H, hd))
        with jax.set_mesh(state.mesh):
            out = jax.jit(
                lambda q, k, v: cp_attention(
                    q, k, v, scale=1.0 / np.sqrt(hd), causal=causal, impl=impl
                )
            )(q, k, v)
        ref = _naive(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5
        )

    @pytest.mark.parametrize("impl", ["ring", "ulysses"])
    def test_gradients_flow(self, impl):
        smp.shutdown()
        smp.init({
            "context_parallel_degree": 2, "ddp": True,
            "context_parallel_impl": impl,
        })
        from smdistributed_modelparallel_tpu.ops.context_parallel import (
            cp_attention,
        )

        B, T, H, hd = 1, 16, 2, 8
        ks = jax.random.split(jax.random.key(1), 3)
        q = jax.random.normal(ks[0], (B, T, H, hd))
        k = jax.random.normal(ks[1], (B, T, H, hd))
        v = jax.random.normal(ks[2], (B, T, H, hd))

        def loss_cp(q, k, v):
            return jnp.sum(
                cp_attention(q, k, v, scale=1.0 / np.sqrt(hd), causal=True,
                             impl=impl) ** 2
            )

        def loss_ref(q, k, v):
            return jnp.sum(_naive(q, k, v) ** 2)

        with jax.set_mesh(state.mesh):
            gc = jax.jit(jax.grad(loss_cp, argnums=(0, 1, 2)))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gc, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


class TestCpEndToEnd:
    @pytest.mark.parametrize("impl", ["ring", "ulysses", "allgather"])
    def test_lmhead_training_parity(self, impl):
        TINY = dict(
            num_layers=2, num_attention_heads=4, attention_head_size=8,
            hidden_size=32, intermediate_size=64, vocab_size=64,
            num_positions=32, causal_mask_size=32, pre_layernorm=True,
            post_layernorm=False, final_layernorm=True,
            attention_dropout_prob=0.0, hidden_dropout_prob=0.0,
            embedding_dropout_prob=0.0,
        )

        def train(cfg):
            smp.shutdown()
            smp.init(cfg)
            m = DistributedTransformerLMHead(**TINY)
            model = smp.DistributedModel(m)
            opt = smp.DistributedOptimizer(optax.sgd(0.1), model)

            @smp.step
            def train_step(model, ids):
                logits = model(ids)
                loss = jnp.mean(
                    vocab_parallel_cross_entropy(logits[:, :-1], ids[:, 1:])
                )
                model.backward(loss)
                return loss

            ids = jax.random.randint(jax.random.key(0), (4, 32), 0, 64)
            losses = []
            for _ in range(2):
                out = train_step(model, ids)
                opt.step()
                losses.append(float(out.reduce_mean()))
            return losses

        base = train({"microbatches": 2})
        cp = train({
            "microbatches": 2, "ddp": True,
            "context_parallel_degree": 4,
            "context_parallel_impl": impl,
        })
        np.testing.assert_allclose(base, cp, atol=1e-4)


class TestCpRealModelFeatures:
    """VERDICT r2 item 9: CP engages for real models — key-padding masks and
    attention dropout run inside the ring/Ulysses regions, with zigzag
    causal load balancing on the ring."""

    def _qkv(self, B=2, T=32, H=4, hd=8):
        ks = jax.random.split(jax.random.key(3), 3)
        return tuple(jax.random.normal(k, (B, T, H, hd)) for k in ks)

    def _kpad(self, B=2, T=32):
        keep = jax.random.bernoulli(jax.random.key(9), 0.8, (B, T))
        return jnp.where(keep, 0.0, -1e4).astype(jnp.float32)

    @pytest.mark.parametrize("impl", ["ring", "ulysses"])
    @pytest.mark.parametrize("causal", [True, False])
    def test_masked_parity(self, impl, causal):
        from smdistributed_modelparallel_tpu.ops.context_parallel import (
            cp_attention,
        )

        smp.shutdown()
        smp.init({"context_parallel_degree": 4, "ddp": True,
                  "context_parallel_impl": impl})
        q, k, v = self._qkv()
        kpad = self._kpad()
        with jax.set_mesh(state.mesh):
            out = jax.jit(lambda q, k, v: cp_attention(
                q, k, v, scale=1.0 / np.sqrt(8), causal=causal,
                impl=impl, kpad=kpad,
            ))(q, k, v)
        s = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32) / np.sqrt(8)
        s = s + kpad[:, None, None, :]
        if causal:
            m = jnp.tril(jnp.ones((32, 32), bool))
            s = jnp.where(m[None, None], s, -1e30)
        ref = jnp.einsum(
            "bhts,bshd->bthd", jax.nn.softmax(s, -1), v.astype(jnp.float32)
        ).astype(q.dtype)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5, err_msg=f"{impl} causal={causal}")

    def test_dropout_ring_matches_ulysses(self):
        """Both impls hash dropout on global indices -> identical outputs."""
        from smdistributed_modelparallel_tpu.ops.context_parallel import (
            cp_attention,
        )

        smp.shutdown()
        smp.init({"context_parallel_degree": 4, "ddp": True})
        q, k, v = self._qkv()
        seed = jnp.int32(77)
        outs = {}
        with jax.set_mesh(state.mesh):
            for impl in ("ring", "ulysses"):
                outs[impl] = np.asarray(jax.jit(lambda q, k, v, _i=impl: cp_attention(
                    q, k, v, scale=1.0 / np.sqrt(8), causal=True, impl=_i,
                    kpad=self._kpad(), dropout_rate=0.2, seed=seed,
                ))(q, k, v))
            np.testing.assert_allclose(outs["ring"], outs["ulysses"], atol=3e-5)
            # and dropout actually drops
            no_drop = np.asarray(jax.jit(lambda q, k, v: cp_attention(
                q, k, v, scale=1.0 / np.sqrt(8), causal=True, impl="ring",
                kpad=self._kpad(),
            ))(q, k, v))
        assert not np.allclose(outs["ring"], no_drop)

    def test_lmhead_mask_dropout_runs_ring_with_ppermute(self):
        """The done-criterion probe: an LMHead step with a padding mask AND
        attention dropout at cp4 lowers through the ring (ppermute in the
        jaxpr) and trains."""
        smp.shutdown()
        smp.init({"context_parallel_degree": 4, "ddp": True,
                  "microbatches": 1, "context_parallel_impl": "ring"})
        module = DistributedTransformerLMHead(
            num_layers=2, num_attention_heads=4, attention_head_size=8,
            hidden_size=32, intermediate_size=64, vocab_size=64,
            num_positions=32, causal_mask_size=32,
            pre_layernorm=True, post_layernorm=False, final_layernorm=True,
            attention_dropout_prob=0.1, hidden_dropout_prob=0.0,
            embedding_dropout_prob=0.0, deterministic=False,
        )
        model = smp.DistributedModel(module)
        ids = jax.random.randint(jax.random.key(0), (2, 32), 0, 64)
        mask = jnp.ones((2, 1, 1, 32), bool).at[:, :, :, -4:].set(False)

        opt = smp.DistributedOptimizer(optax.sgd(0.1), model)

        @smp.step
        def train_step(model, ids):
            logits = model(ids, attention_mask=mask)
            loss = jnp.mean(
                vocab_parallel_cross_entropy(logits[:, :-1], ids[:, 1:])
            )
            model.backward(loss)
            return loss

        losses = []
        for _ in range(3):
            out = train_step(model, ids)
            opt.step()
            losses.append(float(out.reduce_mean()))

        # jaxpr probe: the traced model call must contain a ppermute.
        def fwd(params, ids):
            return module.apply(
                {"params": params}, ids, attention_mask=mask,
                rngs={"dropout": jax.random.key(1)},
            )

        with jax.set_mesh(state.mesh):
            jaxpr = str(jax.make_jaxpr(fwd)(model.params, ids))
        assert "ppermute" in jaxpr, "ring path not engaged"
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0]

    def test_zigzag_relayout_perms_and_roundtrip(self):
        """The in-region zigzag re-layout: the two ppermutes are device
        bijections placing half-chunk h on device _zig_owner(h), and
        enter followed by exit is the identity (checked through a real
        shard_map over the cp axis)."""
        from smdistributed_modelparallel_tpu.ops import context_parallel as cp
        from smdistributed_modelparallel_tpu.backend.topology import CP_AXIS

        for n in (2, 4):
            p1, p2 = cp._zig_perms(n)
            assert sorted(d for _, d in p1) == list(range(n))
            assert sorted(d for _, d in p2) == list(range(n))
            # h=2d goes to owner(2d), h=2d+1 to owner(2d+1).
            for d, dst in p1:
                assert dst == cp._zig_owner(2 * d, n)

        smp.reset()
        smp.init({"context_parallel_degree": 4, "microbatches": 1})
        from smdistributed_modelparallel_tpu.backend.state import state
        from jax.sharding import PartitionSpec as P

        T, n = 32, 4
        x = jnp.arange(2 * T, dtype=jnp.float32).reshape(2, T)

        def body(xl):
            me = jax.lax.axis_index(CP_AXIS)
            z = cp._zig_enter(xl, me, n, CP_AXIS)
            # Each device's zigzag block must be chunks (me, 2n-1-me) of
            # the global sequence: row values are 1-to-1 with positions.
            back = cp._zig_exit(z, me, n, CP_AXIS)
            return back, z

        shard_fn = jax.shard_map(
            body, mesh=state.mesh,
            in_specs=P(None, CP_AXIS),
            out_specs=(P(None, CP_AXIS), P(None, CP_AXIS)),
            axis_names={CP_AXIS}, check_vma=False,
        )
        with jax.set_mesh(state.mesh):
            back, z = jax.jit(shard_fn)(x)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(x))
        # Zigzag global order: device i carries half-chunks i and 2n-1-i.
        half = T // (2 * n)
        expect = []
        for i in range(n):
            expect += list(range(i * half, (i + 1) * half))
            expect += list(range((2 * n - 1 - i) * half, (2 * n - i) * half))
        np.testing.assert_array_equal(np.asarray(z)[0], np.asarray(expect))


class TestCpFlashPath:
    """VERDICT r3 weak #3: the Pallas flash kernels run INSIDE the CP
    manual regions (per ring step / per Ulysses local block) when dropout
    is off, so long-context memory stays O(T) instead of O(Tl^2).
    FORCE_INTERPRET exercises the exact dispatch on the CPU tier."""

    @pytest.fixture(autouse=True)
    def _force_interpret(self):
        from smdistributed_modelparallel_tpu.ops import pallas_attention as pk
        from smdistributed_modelparallel_tpu.ops import context_parallel as cp

        pk.FORCE_INTERPRET = True
        cp._ring_flash_fn.cache_clear()
        cp._build_cp_call.cache_clear()
        yield
        pk.FORCE_INTERPRET = False
        cp._ring_flash_fn.cache_clear()
        cp._build_cp_call.cache_clear()

    def _qkv(self, B=2, T=32, H=4, hd=8):
        ks = jax.random.split(jax.random.key(3), 3)
        return tuple(jax.random.normal(k, (B, T, H, hd)) for k in ks)

    def _kpad(self, B=2, T=32):
        keep = jax.random.bernoulli(jax.random.key(9), 0.8, (B, T))
        return jnp.where(keep, 0.0, -1e4).astype(jnp.float32)

    def test_flash_dispatch_engages(self, monkeypatch):
        """The parity tests below are meaningless if dispatch silently
        falls back to jnp — count the blockwise-kernel calls."""
        from smdistributed_modelparallel_tpu.ops import pallas_attention as pk
        from smdistributed_modelparallel_tpu.ops.context_parallel import (
            cp_attention,
        )

        calls = []
        orig = pk.flash_fwd_with_ids
        monkeypatch.setattr(
            pk, "flash_fwd_with_ids",
            lambda *a, **kw: calls.append(1) or orig(*a, **kw),
        )
        smp.shutdown()
        smp.init({"context_parallel_degree": 4, "ddp": True})
        q, k, v = self._qkv()
        with jax.set_mesh(state.mesh):
            jax.jit(lambda q, k, v: cp_attention(
                q, k, v, scale=1.0 / np.sqrt(8), causal=True, impl="ring"
            ))(q, k, v)
        # The ring steps are a fori_loop, so the blockwise kernel traces
        # once; any call at all proves the flash body was dispatched.
        assert len(calls) == 1

    @pytest.mark.parametrize("causal", [True, False])
    def test_chunked_ring_parity(self, monkeypatch, causal):
        """Per-shard blocks beyond _RING_CHUNK split into n_sub kernel
        calls per ring step (fwd) and n_sub^2 (bwd); outputs and grads
        must match the jnp ring body bit-for-bit in pattern (dropout on,
        kpad on) and numerically everywhere."""
        from smdistributed_modelparallel_tpu.ops import pallas_attention as pk
        from smdistributed_modelparallel_tpu.ops import context_parallel as cp

        # Tl = 32/4 = 8; chunk 4 -> n_sub = 2.
        monkeypatch.setattr(cp, "_RING_CHUNK", 4)
        calls = []
        orig = pk.flash_fwd_with_ids
        monkeypatch.setattr(
            pk, "flash_fwd_with_ids",
            lambda *a, **kw: calls.append(a[1].shape) or orig(*a, **kw),
        )
        q, k, v = self._qkv()
        kp = self._kpad()
        seed = jnp.int32(11)
        grads, outs = {}, {}
        for pallas in (True, False):
            smp.shutdown()
            smp.init({"context_parallel_degree": 4, "ddp": True,
                      "use_pallas_kernels": pallas})
            cp._build_cp_call.cache_clear()
            cp._ring_flash_fn.cache_clear()

            def loss(q, k, v):
                out = cp.cp_attention(
                    q, k, v, scale=1.0 / np.sqrt(8), causal=causal,
                    impl="ring", kpad=kp, dropout_rate=0.2, seed=seed,
                )
                return jnp.sum(out ** 2), out

            with jax.set_mesh(state.mesh):
                g, out = jax.jit(jax.grad(
                    loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
            grads[pallas], outs[pallas] = g, out
        # The flash run chunked the KV blocks to length 4.
        assert calls and all(s[1] == 4 for s in calls), calls
        np.testing.assert_allclose(np.asarray(outs[True]),
                                   np.asarray(outs[False]), atol=3e-5)
        for a, b in zip(grads[True], grads[False]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4)

    @pytest.mark.parametrize("causal", [True, False])
    def test_chunked_ulysses_parity(self, monkeypatch, causal):
        """Global sequences beyond _RING_CHUNK run the chunked full-flash
        body after the Ulysses all_to_all (n_sub kv chunks fwd, n_sub^2
        bwd); outputs and grads must match the jnp Ulysses body with
        dropout (global head0 hash) and kpad active."""
        from smdistributed_modelparallel_tpu.ops import pallas_attention as pk
        from smdistributed_modelparallel_tpu.ops import context_parallel as cp

        # T = 32; chunk 16 -> n_sub = 2 for the full-T Ulysses sequence.
        monkeypatch.setattr(cp, "_RING_CHUNK", 16)
        calls = []
        orig = pk.flash_fwd_with_ids
        monkeypatch.setattr(
            pk, "flash_fwd_with_ids",
            lambda *a, **kw: calls.append(a[1].shape) or orig(*a, **kw),
        )
        q, k, v = self._qkv()
        kp = self._kpad()
        seed = jnp.int32(23)
        grads, outs = {}, {}
        for pallas in (True, False):
            smp.shutdown()
            smp.init({"context_parallel_degree": 4, "ddp": True,
                      "context_parallel_impl": "ulysses",
                      "use_pallas_kernels": pallas})
            cp._build_cp_call.cache_clear()
            cp._chunked_full_flash_fn.cache_clear()

            def loss(q, k, v):
                out = cp.cp_attention(
                    q, k, v, scale=1.0 / np.sqrt(8), causal=causal,
                    impl="ulysses", kpad=kp, dropout_rate=0.2, seed=seed,
                )
                return jnp.sum(out ** 2), out

            with jax.set_mesh(state.mesh):
                g, out = jax.jit(jax.grad(
                    loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
            grads[pallas], outs[pallas] = g, out
        # The flash run chunked the post-exchange kv to length 16.
        assert calls and all(s[1] == 16 for s in calls), calls
        np.testing.assert_allclose(np.asarray(outs[True]),
                                   np.asarray(outs[False]), atol=3e-5)
        for a, b in zip(grads[True], grads[False]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4)

    def test_ring_chunks_split_selection(self):
        from smdistributed_modelparallel_tpu.ops.context_parallel import (
            _ring_chunks,
        )

        assert _ring_chunks(4096, 8192) == 1
        assert _ring_chunks(8192, 8192) == 1
        assert _ring_chunks(16384, 8192) == 2
        assert _ring_chunks(32768, 8192) == 4
        assert _ring_chunks(3 * 8192, 8192) == 3
        assert _ring_chunks(40960, 8192) == 5
        # No split with chunks >= 128: falls back (and warns).
        assert _ring_chunks(64, 8192) is None
        prime = 13 * 8191
        assert _ring_chunks(prime, 8192) == 13  # 8191 <= 8192, divides

    @pytest.mark.parametrize("impl", ["ring", "ulysses"])
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("use_kpad", [False, True])
    def test_flash_parity(self, impl, causal, use_kpad):
        from smdistributed_modelparallel_tpu.ops.context_parallel import (
            cp_attention,
        )

        smp.shutdown()
        smp.init({"context_parallel_degree": 4, "ddp": True,
                  "context_parallel_impl": impl})
        q, k, v = self._qkv()
        kp = self._kpad() if use_kpad else None
        with jax.set_mesh(state.mesh):
            out = jax.jit(lambda q, k, v: cp_attention(
                q, k, v, scale=1.0 / np.sqrt(8), causal=causal, impl=impl,
                kpad=kp,
            ))(q, k, v)
        ref = _naive(q, k, v, causal, kp)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5)

    @pytest.mark.parametrize("impl", ["ring", "ulysses"])
    def test_flash_gradients(self, impl):
        from smdistributed_modelparallel_tpu.ops.context_parallel import (
            cp_attention,
        )

        smp.shutdown()
        smp.init({"context_parallel_degree": 4, "ddp": True,
                  "context_parallel_impl": impl})
        q, k, v = self._qkv()
        kp = self._kpad()

        def loss_cp(q, k, v):
            return jnp.sum(cp_attention(
                q, k, v, scale=1.0 / np.sqrt(8), causal=True, impl=impl,
                kpad=kp,
            ) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(_naive(q, k, v, True, kp) ** 2)

        with jax.set_mesh(state.mesh):
            gc = jax.jit(jax.grad(loss_cp, argnums=(0, 1, 2)))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gc, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4)

    def test_dropout_flash_matches_jnp_across_impls(self):
        """Dropout inside the flash CP paths: the kernels hash on GLOBAL
        (bh, row, col) ids with the T stride, so flash-ring, flash-Ulysses,
        jnp-ring, and jnp-Ulysses all produce the SAME dropped pattern for
        one (model, seed)."""
        from smdistributed_modelparallel_tpu.ops import context_parallel as cp
        from smdistributed_modelparallel_tpu.ops.context_parallel import (
            cp_attention,
        )

        q, k, v = self._qkv()
        kp = self._kpad()
        seed = jnp.int32(77)
        outs = {}
        for impl in ("ring", "ulysses"):
            for pallas in (True, False):
                smp.shutdown()
                smp.init({"context_parallel_degree": 4, "ddp": True,
                          "use_pallas_kernels": pallas})
                cp._build_cp_call.cache_clear()
                cp._ring_flash_fn.cache_clear()
                with jax.set_mesh(state.mesh):
                    outs[(impl, pallas)] = np.asarray(jax.jit(
                        lambda q, k, v, _i=impl: cp_attention(
                            q, k, v, scale=1.0 / np.sqrt(8), causal=True,
                            impl=_i, kpad=kp, dropout_rate=0.2, seed=seed,
                        )
                    )(q, k, v))
        ref = outs[("ring", False)]
        for key, val in outs.items():
            np.testing.assert_allclose(val, ref, atol=3e-5, err_msg=str(key))
        # ...and dropout actually dropped something.
        smp.shutdown()
        smp.init({"context_parallel_degree": 4, "ddp": True})
        with jax.set_mesh(state.mesh):
            nodrop = np.asarray(jax.jit(lambda q, k, v: cp_attention(
                q, k, v, scale=1.0 / np.sqrt(8), causal=True, impl="ring",
                kpad=kp,
            ))(q, k, v))
        assert not np.allclose(ref, nodrop)

    @pytest.mark.parametrize("impl", ["ring", "ulysses"])
    def test_dropout_flash_gradients_match_jnp(self, impl):
        """Same seed -> same mask -> the flash custom-VJP/AD gradients must
        match reverse-AD through the jnp bodies. The Ulysses case also
        covers the head0 remap through the backward kernels."""
        from smdistributed_modelparallel_tpu.ops import context_parallel as cp
        from smdistributed_modelparallel_tpu.ops.context_parallel import (
            cp_attention,
        )

        q, k, v = self._qkv()
        seed = jnp.int32(5)
        grads = {}
        for pallas in (True, False):
            smp.shutdown()
            smp.init({"context_parallel_degree": 4, "ddp": True,
                      "use_pallas_kernels": pallas})
            cp._build_cp_call.cache_clear()
            cp._ring_flash_fn.cache_clear()

            def loss(q, k, v):
                return jnp.sum(cp_attention(
                    q, k, v, scale=1.0 / np.sqrt(8), causal=True,
                    impl=impl, dropout_rate=0.2, seed=seed,
                ) ** 2)

            with jax.set_mesh(state.mesh):
                grads[pallas] = jax.jit(
                    jax.grad(loss, argnums=(0, 1, 2))
                )(q, k, v)
        for a, b in zip(grads[True], grads[False]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4)

    @pytest.mark.slow
    def test_no_score_block_materialized_at_8k(self):
        """The done-criterion probe (VERDICT r3 next-round #3): at cp4 /
        T=8k, the compiled fwd+bwd ring step must allocate LESS temp
        memory than ONE [Tl, Tl] fp32 score block — proof that neither
        the forward nor the AD backward materializes score matrices or
        stashes rotating KV carries. The jnp ring body is the
        counterfactual (~20x more temp)."""
        from smdistributed_modelparallel_tpu.ops import pallas_attention as pk
        from smdistributed_modelparallel_tpu.ops import context_parallel as cp

        smp.shutdown()
        smp.init({"context_parallel_degree": 4, "ddp": True})
        B, T, H, hd = 1, 8192, 1, 64
        Tl = T // 4
        ks = jax.random.split(jax.random.key(0), 3)
        q, k, v = (
            jax.random.normal(kk, (B, T, H, hd), jnp.float32) for kk in ks
        )

        def loss(q, k, v):
            return jnp.sum(cp.cp_attention(
                q, k, v, scale=1.0 / np.sqrt(hd), causal=True, impl="ring"
            ) ** 2)

        temps = {}
        for mode in ("flash", "jnp"):
            pk.FORCE_INTERPRET = mode == "flash"
            cp._build_cp_call.cache_clear()
            cp._ring_flash_fn.cache_clear()
            with jax.set_mesh(state.mesh):
                compiled = (
                    jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
                    .lower(q, k, v).compile()
                )
            temps[mode] = compiled.memory_analysis().temp_size_in_bytes
        block_bytes = Tl * Tl * 4
        assert temps["flash"] < block_bytes, temps
        assert temps["jnp"] > 4 * block_bytes, temps  # the counterfactual

    @pytest.mark.slow
    def test_no_score_block_materialized_at_64k(self):
        """VERDICT r4 ask #2: the r3 proof repeated at cp4 / T=64k
        (Tl=16k) — beyond the kernels' single-call envelope, so the
        chunked dispatch (n_sub=2) carries it. The compiled fwd+bwd ring
        step must still allocate less temp memory than ONE [Tl, Tl] fp32
        score block (1 GiB here); no jnp counterfactual at this size (it
        would materialize exactly that block)."""
        from smdistributed_modelparallel_tpu.ops import pallas_attention as pk
        from smdistributed_modelparallel_tpu.ops import context_parallel as cp

        smp.shutdown()
        smp.init({"context_parallel_degree": 4, "ddp": True})
        B, T, H, hd = 1, 65536, 1, 64
        Tl = T // 4
        assert cp._ring_chunks(Tl, cp._RING_CHUNK, min_len=1) == 2
        ks = jax.random.split(jax.random.key(0), 3)
        q, k, v = (
            jax.random.normal(kk, (B, T, H, hd), jnp.float32) for kk in ks
        )

        def loss(q, k, v):
            return jnp.sum(cp.cp_attention(
                q, k, v, scale=1.0 / np.sqrt(hd), causal=True, impl="ring"
            ) ** 2)

        pk.FORCE_INTERPRET = True
        cp._build_cp_call.cache_clear()
        cp._ring_flash_fn.cache_clear()
        try:
            with jax.set_mesh(state.mesh):
                compiled = (
                    jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
                    .lower(q, k, v).compile()
                )
        finally:
            pk.FORCE_INTERPRET = False
            cp._build_cp_call.cache_clear()
            cp._ring_flash_fn.cache_clear()
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < Tl * Tl * 4, temp

    @pytest.mark.slow
    def test_ulysses_no_score_block_materialized_at_32k(self):
        """Chunked Ulysses at cp4 / T=32k (n_sub=4 over the full
        post-exchange sequence): the compiled fwd+bwd step must allocate
        less temp memory than ONE [T, T] fp32 score matrix — the jnp body
        would materialize exactly that."""
        from smdistributed_modelparallel_tpu.ops import pallas_attention as pk
        from smdistributed_modelparallel_tpu.ops import context_parallel as cp

        smp.shutdown()
        smp.init({"context_parallel_degree": 4, "ddp": True,
                  "context_parallel_impl": "ulysses"})
        B, T, H, hd = 1, 32768, 4, 64
        assert cp._ring_chunks(T, cp._RING_CHUNK, min_len=1) == 4
        ks = jax.random.split(jax.random.key(0), 3)
        q, k, v = (
            jax.random.normal(kk, (B, T, H, hd), jnp.float32) for kk in ks
        )

        def loss(q, k, v):
            return jnp.sum(cp.cp_attention(
                q, k, v, scale=1.0 / np.sqrt(hd), causal=True,
                impl="ulysses",
            ) ** 2)

        pk.FORCE_INTERPRET = True
        cp._build_cp_call.cache_clear()
        cp._chunked_full_flash_fn.cache_clear()
        try:
            with jax.set_mesh(state.mesh):
                compiled = (
                    jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
                    .lower(q, k, v).compile()
                )
        finally:
            pk.FORCE_INTERPRET = False
            cp._build_cp_call.cache_clear()
            cp._chunked_full_flash_fn.cache_clear()
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < T * T * 4, temp

    def test_fallback_to_jnp_body_warns_once(self, monkeypatch):
        """When the flash path is unavailable on TPU (here: per-shard
        length below the kernel floor), dispatch must fall back to the
        jnp body WITH a log line — the silent r4 pathology — and warn
        once per shape, not per call."""
        import logging

        from smdistributed_modelparallel_tpu.ops import context_parallel as cp
        from smdistributed_modelparallel_tpu.utils.logger import get_logger

        smp.shutdown()
        smp.init({"context_parallel_degree": 4, "ddp": True})
        # Pretend we're on TPU for dispatch; the chosen jnp body runs
        # fine on CPU (the flash path cannot engage at Tl=8 < 128).
        monkeypatch.setattr(cp.jax, "default_backend", lambda: "tpu")
        cp._FALLBACK_WARNED.clear()
        q, k, v = self._qkv()

        records = []

        class Capture(logging.Handler):
            def emit(self, record):
                records.append(record.getMessage())

        handler = Capture()
        get_logger().addHandler(handler)
        try:
            with jax.set_mesh(state.mesh):
                for _ in range(2):
                    jax.jit(lambda q, k, v: cp.cp_attention(
                        q, k, v, scale=1.0 / np.sqrt(8), causal=True,
                        impl="ring",
                    ))(q, k, v)
        finally:
            get_logger().removeHandler(handler)
        warned = [m for m in records if "score-materializing" in m]
        assert len(warned) == 1, records
