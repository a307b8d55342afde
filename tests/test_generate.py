"""smp.generate: KV-cache autoregressive decoding.

Strategy (SURVEY §4 parity-tier style): the decode path must reproduce the
*training* forward exactly — every greedy continuation is checked against a
naive loop that re-runs the full (cache-less) forward per token. Tiers:
unit (sampling filters), parity (zoo + nn families, rotary/learned/window),
distributed parity (tp4 mesh == single-device), behavior (EOS freeze,
temperature reproducibility).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import smdistributed_modelparallel_tpu as smp
from smdistributed_modelparallel_tpu.generation import (
    _top_k_filter,
    _top_p_filter,
)
from smdistributed_modelparallel_tpu.models.transformer_lm import TransformerLM
from smdistributed_modelparallel_tpu.nn.transformer import (
    DistributedTransformerLMHead,
)
from smdistributed_modelparallel_tpu.utils.exceptions import SMPValidationError


def _greedy_reference(module, params, ids, steps):
    """Cache-less greedy loop: full forward per new token."""
    cur = ids
    for _ in range(steps):
        logits = module.apply({"params": params}, cur)
        nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), -1)
        cur = jnp.concatenate([cur, nxt[:, None].astype(cur.dtype)], 1)
    return np.asarray(cur)


def _zoo(pos_type="learned", **kw):
    kw.setdefault("vocab_size", 97)
    kw.setdefault("max_len", 64)
    kw.setdefault("d_model", 32)
    kw.setdefault("n_layers", 2)
    kw.setdefault("n_heads", 4)
    return TransformerLM(pos_type=pos_type, **kw)


class TestSamplingFilters:
    def test_top_k_keeps_k(self):
        logits = jnp.asarray([[5.0, 1.0, 3.0, 2.0, 4.0]])
        out = _top_k_filter(logits, 2)
        np.testing.assert_array_equal(
            np.isfinite(np.asarray(out))[0], [True, False, False, False, True]
        )

    def test_top_p_always_keeps_argmax(self):
        logits = jnp.asarray([[10.0, 0.0, -1.0]])
        out = _top_p_filter(logits, 0.01)
        assert np.isfinite(np.asarray(out))[0, 0]
        assert not np.isfinite(np.asarray(out))[0, 1:].any()

    def test_top_p_keeps_nucleus(self):
        # probs ~ [0.6, 0.25, 0.1, ...]: top_p=0.7 keeps the first two.
        probs = np.asarray([0.6, 0.25, 0.1, 0.05])
        logits = jnp.log(jnp.asarray(probs))[None]
        out = np.isfinite(np.asarray(_top_p_filter(logits, 0.7)))[0]
        np.testing.assert_array_equal(out, [True, True, False, False])


class TestZooGreedyParity:
    @pytest.mark.parametrize("pos_type", ["learned", "rotary", "none"])
    def test_matches_cacheless_forward(self, pos_type):
        smp.init({})
        mod = _zoo(pos_type)
        ids = jax.random.randint(jax.random.key(1), (2, 7), 0, 97)
        params = mod.init(jax.random.key(0), ids)["params"]
        want = _greedy_reference(mod, params, ids, 6)
        got = np.asarray(smp.generate(mod, ids, 6, params=params))
        np.testing.assert_array_equal(got, want)

    def test_windowed_attention(self):
        smp.init({})
        mod = _zoo("rotary", window=4)
        ids = jax.random.randint(jax.random.key(2), (2, 6), 0, 97)
        params = mod.init(jax.random.key(0), ids)["params"]
        want = _greedy_reference(mod, params, ids, 5)
        got = np.asarray(smp.generate(mod, ids, 5, params=params))
        np.testing.assert_array_equal(got, want)

    def test_parallel_block(self):
        smp.init({})
        mod = _zoo("rotary", parallel_block=True)
        ids = jax.random.randint(jax.random.key(3), (1, 5), 0, 97)
        params = mod.init(jax.random.key(0), ids)["params"]
        want = _greedy_reference(mod, params, ids, 4)
        got = np.asarray(smp.generate(mod, ids, 4, params=params))
        np.testing.assert_array_equal(got, want)


class TestNnFamilyGreedyParity:
    def _head(self, **kw):
        kw.setdefault("num_layers", 2)
        kw.setdefault("num_attention_heads", 4)
        kw.setdefault("attention_head_size", 8)
        kw.setdefault("hidden_size", 32)
        kw.setdefault("intermediate_size", 64)
        kw.setdefault("vocab_size", 97)
        kw.setdefault("num_positions", 64)
        kw.setdefault("causal_mask_size", 64)
        kw.setdefault("attention_dropout_prob", 0.0)
        kw.setdefault("hidden_dropout_prob", 0.0)
        kw.setdefault("embedding_dropout_prob", 0.0)
        kw.setdefault("deterministic", True)
        return DistributedTransformerLMHead(**kw)

    @pytest.mark.parametrize(
        "kw",
        [
            {},  # GPT-2-style: learned positions, post-LN
            {   # GPT-J-style: rotary, parallel residual, final LN
                "use_positional_embedding": False,
                "rotary_dim": 8,
                "parallel_attn_output": True,
                "single_pre_layernorm": True,
                "post_layernorm": False,
                "final_layernorm": True,
            },
            {   # NeoX-style rotary
                "use_positional_embedding": False,
                "rotary_dim": 8,
                "gpt_neox_type_rotary": True,
                "pre_layernorm": True,
                "post_layernorm": False,
                "final_layernorm": True,
            },
        ],
        ids=["gpt2_style", "gptj_style", "neox_style"],
    )
    def test_matches_cacheless_forward(self, kw):
        smp.init({})
        mod = self._head(**kw)
        ids = jax.random.randint(jax.random.key(4), (2, 6), 0, 97)
        params = mod.init(jax.random.key(0), ids)["params"]
        want = _greedy_reference(mod, params, ids, 5)
        got = np.asarray(smp.generate(mod, ids, 5, params=params))
        np.testing.assert_array_equal(got, want)

    def test_bert_family_refuses_decode(self):
        smp.init({})
        mod = self._head(causal_mask_size=None)
        ids = jnp.zeros((1, 4), jnp.int32)
        params = mod.init(jax.random.key(0), ids)["params"]
        with pytest.raises(SMPValidationError):
            smp.generate(mod, ids, 2, params=params)


class TestDistributedParity:
    @pytest.mark.parametrize(
        "tp,head,vocab_shards",
        [
            (4, {}, None),
            # An untied head's logits arrive split on the vocabulary over
            # tp (PR 30); sampling reads them whole.
            (2, {"tie_input_output_embedding": False, "vocab_size": 96}, 2),
        ],
        ids=["tied_tp4", "untied_vocab_split_tp2"],
    )
    def test_tp_matches_single_device(self, tp, head, vocab_shards):
        # The same weights must generate the same tokens on a tp mesh as
        # on one device (parity-tier pattern used across the suite).
        from smdistributed_modelparallel_tpu.utils.telemetry import telemetry

        smp.init({})
        mod = self._nn_head(**head)
        ids = jax.random.randint(jax.random.key(5), (2, 6), 0, 96)
        params = mod.init(jax.random.key(0), ids)["params"]
        single = np.asarray(smp.generate(mod, ids, 5, params=params))

        smp.reset()
        smp.init({"tensor_parallel_degree": tp, "ddp": True})
        got = np.asarray(smp.generate(mod, ids, 5, params=params))
        np.testing.assert_array_equal(got, single)
        if vocab_shards is not None:
            gauge = telemetry.report()["metrics"]["smp_lm_head_vocab_shards"]
            assert [s["value"] for s in gauge["series"]] == [vocab_shards]

    @staticmethod
    def _nn_head(**kw):
        kw = dict(dict(
            num_layers=2,
            num_attention_heads=4,
            attention_head_size=8,
            hidden_size=32,
            intermediate_size=64,
            vocab_size=97,
            num_positions=64,
            causal_mask_size=64,
            attention_dropout_prob=0.0,
            hidden_dropout_prob=0.0,
            embedding_dropout_prob=0.0,
            deterministic=True,
        ), **kw)
        return DistributedTransformerLMHead(**kw)

    def test_wrapped_model_generate(self):
        smp.init({"tensor_parallel_degree": 2, "ddp": True})
        model = smp.DistributedModel(self._nn_head())
        ids = jax.random.randint(jax.random.key(6), (2, 5), 0, 97)
        out = model.generate(ids, 4)
        assert out.shape == (2, 9)
        # Continuation must match the wrapped module's cache-less greedy.
        want = _greedy_reference(model.module, model.params, ids, 4)
        np.testing.assert_array_equal(np.asarray(out), want)

    def test_generate_after_pp_training(self):
        """VERDICT r4 ask #3: train at pp2 x tp2, then sample WITHOUT a
        topology change — the pp-sharded layer stacks regather for
        decode, token-exact with a pp=1 run of the same trained
        weights."""
        import optax

        smp.init({"pipeline_parallel_degree": 2, "tensor_parallel_degree": 2,
                  "ddp": True, "microbatches": 2})
        model = smp.DistributedModel(self._nn_head())
        optimizer = smp.DistributedOptimizer(optax.adamw(1e-3), model)

        @smp.step
        def train_step(model, ids):
            logits = model(ids)
            lg = logits[:, :-1]
            tgt = jnp.take_along_axis(lg, ids[:, 1:, None], axis=-1)[..., 0]
            lse = jax.scipy.special.logsumexp(
                lg.astype(jnp.float32), axis=-1
            )
            loss = jnp.mean(lse - tgt.astype(jnp.float32))
            model.backward(loss)
            return loss

        batch = jax.random.randint(jax.random.key(8), (4, 16), 0, 97)
        for _ in range(2):
            train_step(model, batch)
            optimizer.step()

        prompts = jax.random.randint(jax.random.key(9), (2, 6), 0, 97)
        out_mid = np.asarray(model.generate(prompts, 5))
        # Regathered decode params are cached by params identity.
        cache = model._decode_params_cache
        assert cache is not None and cache[0] is model.params
        out_mid2 = np.asarray(model.generate(prompts, 5))
        assert model._decode_params_cache is cache
        np.testing.assert_array_equal(out_mid, out_mid2)
        # The next optimizer step replaces the params and must drop the
        # regathered decode copy (it would otherwise pin a full-size
        # param tree in memory through the rest of training).
        train_step(model, batch)
        optimizer.step()
        assert model._decode_params_cache is None

        trained = model.state_dict()
        out_pp = np.asarray(model.generate(prompts, 5))
        beams_pp = np.asarray(model.generate(prompts, 5, num_beams=2))

        # Reference: the same trained weights on a pp=1 tp2 mesh.
        smp.reset()
        smp.init({"tensor_parallel_degree": 2, "ddp": True})
        ref_model = smp.DistributedModel(self._nn_head())
        ref_model._eager_init((prompts,), {})
        ref_model.load_state_dict(trained)
        out_1 = np.asarray(ref_model.generate(prompts, 5))
        beams_1 = np.asarray(ref_model.generate(prompts, 5, num_beams=2))
        np.testing.assert_array_equal(out_pp, out_1)
        np.testing.assert_array_equal(beams_pp, beams_1)


class TestSamplingBehavior:
    def test_eos_freezes_rows(self):
        smp.init({})
        mod = _zoo("learned")
        ids = jax.random.randint(jax.random.key(7), (2, 5), 0, 97)
        params = mod.init(jax.random.key(0), ids)["params"]
        # Find the first greedily-emitted token and declare it EOS: the
        # remaining positions of that row must be pad.
        ref = _greedy_reference(mod, params, ids, 4)
        eos = int(ref[0, 5])
        got = np.asarray(
            smp.generate(mod, ids, 4, params=params, eos_token_id=eos,
                         pad_token_id=0)
        )
        assert got[0, 5] == eos
        np.testing.assert_array_equal(got[0, 6:], 0)

    def test_sampling_reproducible_and_rng_sensitive(self):
        smp.init({})
        mod = _zoo("learned")
        ids = jax.random.randint(jax.random.key(8), (2, 5), 0, 97)
        params = mod.init(jax.random.key(0), ids)["params"]
        a = np.asarray(
            smp.generate(mod, ids, 8, params=params, temperature=1.0,
                         rng=jax.random.key(1))
        )
        b = np.asarray(
            smp.generate(mod, ids, 8, params=params, temperature=1.0,
                         rng=jax.random.key(1))
        )
        c = np.asarray(
            smp.generate(mod, ids, 8, params=params, temperature=1.0,
                         rng=jax.random.key(2))
        )
        np.testing.assert_array_equal(a, b)
        assert not np.array_equal(a, c)

    def test_top_k_one_is_greedy(self):
        smp.init({})
        mod = _zoo("learned")
        ids = jax.random.randint(jax.random.key(9), (2, 5), 0, 97)
        params = mod.init(jax.random.key(0), ids)["params"]
        want = _greedy_reference(mod, params, ids, 5)
        got = np.asarray(
            smp.generate(mod, ids, 5, params=params, temperature=0.7,
                         top_k=1, rng=jax.random.key(3))
        )
        np.testing.assert_array_equal(got, want)

    def test_requires_rng_when_sampling(self):
        smp.init({})
        mod = _zoo("learned")
        ids = jnp.zeros((1, 4), jnp.int32)
        params = mod.init(jax.random.key(0), ids)["params"]
        with pytest.raises(SMPValidationError):
            smp.generate(mod, ids, 2, params=params, temperature=1.0)

    def test_greedy_with_filters_refused(self):
        # top_k/top_p are silently inert under temperature == 0 — refuse
        # rather than hand back greedy output the user didn't ask for.
        smp.init({})
        mod = _zoo("learned")
        ids = jnp.zeros((1, 4), jnp.int32)
        params = mod.init(jax.random.key(0), ids)["params"]
        with pytest.raises(SMPValidationError, match="no effect"):
            smp.generate(mod, ids, 2, params=params, top_p=0.9)
        with pytest.raises(SMPValidationError, match="no effect"):
            smp.generate(mod, ids, 2, params=params, top_k=5)

    def test_filter_ranges_validated(self):
        smp.init({})
        mod = _zoo("learned")
        ids = jnp.zeros((1, 4), jnp.int32)
        params = mod.init(jax.random.key(0), ids)["params"]
        rng = jax.random.key(0)
        with pytest.raises(SMPValidationError, match="temperature"):
            smp.generate(mod, ids, 2, params=params, temperature=-0.5,
                         top_p=0.9)
        with pytest.raises(SMPValidationError, match="top_k"):
            smp.generate(mod, ids, 2, params=params, temperature=1.0,
                         top_k=0, rng=rng)
        with pytest.raises(SMPValidationError, match="top_p"):
            smp.generate(mod, ids, 2, params=params, temperature=1.0,
                         top_p=0.0, rng=rng)

    def test_position_limit_enforced(self):
        smp.init({})
        mod = _zoo("learned", max_len=16)
        ids = jnp.zeros((1, 10), jnp.int32)
        params = mod.init(jax.random.key(0), ids)["params"]
        with pytest.raises(SMPValidationError):
            smp.generate(mod, ids, 10, params=params)

    def test_pp_raw_module_without_params_refused(self):
        # Under pp, auto-regather needs a DistributedModel; a raw flax
        # module must come with explicit params.
        smp.init({"pipeline_parallel_degree": 2, "microbatches": 2})
        mod = _zoo("learned")
        ids = jnp.zeros((1, 4), jnp.int32)
        with pytest.raises(SMPValidationError, match="regather"):
            smp.generate(mod, ids, 2)

    def test_zero_new_tokens_refused(self):
        smp.init({})
        mod = _zoo("learned")
        ids = jnp.zeros((1, 4), jnp.int32)
        with pytest.raises(SMPValidationError):
            smp.generate(mod, ids, 0, params={})

    def test_multi_token_chunk_on_nonempty_cache_refused(self):
        # The KV-cache protocol: only the FIRST (cache-creating) call may
        # carry a multi-token chunk; a later chunk would silently ignore
        # the cached positions, so it must raise instead.
        smp.init({})
        mod = _zoo("learned").clone(decode=True, decode_cache_len=16)
        ids = jnp.zeros((1, 4), jnp.int32)
        params = mod.init(jax.random.key(0), ids)["params"]
        _, mut = mod.apply({"params": params}, ids, mutable=["cache"])
        with pytest.raises(ValueError, match="protocol"):
            mod.apply(
                {"params": params, "cache": mut["cache"]}, ids,
                mutable=["cache"],
            )


class TestSeq2SeqGreedyParity:
    @staticmethod
    def _enc_dec(**kw):
        from smdistributed_modelparallel_tpu.models.encoder_decoder import (
            EncoderDecoderLM,
        )

        kw.setdefault("vocab_size", 89)
        kw.setdefault("d_model", 32)
        kw.setdefault("enc_layers", 2)
        kw.setdefault("dec_layers", 2)
        kw.setdefault("n_heads", 4)
        kw.setdefault("d_ff", 64)
        kw.setdefault("max_len", 32)
        kw.setdefault("deterministic", True)
        return EncoderDecoderLM(**kw)

    @staticmethod
    def _greedy_reference(mod, params, enc_ids, steps, start_id,
                          enc_mask=None):
        cur = jnp.full((enc_ids.shape[0], 1), start_id, enc_ids.dtype)
        for _ in range(steps):
            logits = mod.apply({"params": params}, enc_ids, cur, enc_mask)
            nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), -1)
            cur = jnp.concatenate([cur, nxt[:, None].astype(cur.dtype)], 1)
        return np.asarray(cur)

    def test_seq2seq_generate_after_pp_training(self):
        """Seq2seq under the pp-then-sample workflow: pp splits the
        DECODER stack, so the regathered decode must reassemble it —
        token-exact with a pp=1 run of the same trained weights."""
        import optax

        smp.init({"pipeline_parallel_degree": 2, "tensor_parallel_degree": 2,
                  "ddp": True, "microbatches": 2})
        model = smp.DistributedModel(self._enc_dec(t5_compat=True))
        optimizer = smp.DistributedOptimizer(optax.adamw(1e-3), model)

        @smp.step
        def train_step(model, enc_ids, dec_ids):
            logits = model(enc_ids, dec_ids)
            lg = logits[:, :-1]
            tgt = jnp.take_along_axis(
                lg, dec_ids[:, 1:, None], axis=-1
            )[..., 0]
            lse = jax.scipy.special.logsumexp(
                lg.astype(jnp.float32), axis=-1
            )
            loss = jnp.mean(lse - tgt.astype(jnp.float32))
            model.backward(loss)
            return loss

        enc = jax.random.randint(jax.random.key(4), (4, 12), 0, 89)
        dec = jax.random.randint(jax.random.key(5), (4, 12), 0, 89)
        train_step(model, enc, dec)
        optimizer.step()
        trained = model.state_dict()

        prompts = jax.random.randint(jax.random.key(6), (2, 8), 0, 89)
        out_pp = np.asarray(model.generate(prompts, 5))

        smp.reset()
        smp.init({"tensor_parallel_degree": 2, "ddp": True})
        ref_model = smp.DistributedModel(self._enc_dec(t5_compat=True))
        ref_model._eager_init((prompts, prompts[:, :1]), {})
        ref_model.load_state_dict(trained)
        out_1 = np.asarray(ref_model.generate(prompts, 5))
        np.testing.assert_array_equal(out_pp, out_1)

    @pytest.mark.parametrize("t5_compat", [False, True],
                             ids=["learned_pos", "t5_rel_bias"])
    def test_matches_cacheless_forward(self, t5_compat):
        smp.init({})
        mod = self._enc_dec(t5_compat=t5_compat)
        enc_ids = jax.random.randint(jax.random.key(20), (2, 9), 0, 89)
        params = mod.init(
            jax.random.key(0), enc_ids, enc_ids[:, :1]
        )["params"]
        want = self._greedy_reference(mod, params, enc_ids, 5, 3)
        got = np.asarray(
            smp.generate(mod, enc_ids, 5, params=params,
                         decoder_start_token_id=3)
        )
        np.testing.assert_array_equal(got, want)

    def test_encoder_padding_mask_honored(self):
        smp.init({})
        mod = self._enc_dec(t5_compat=True)
        enc_ids = jax.random.randint(jax.random.key(21), (2, 8), 0, 89)
        mask = jnp.asarray([[1] * 8, [1] * 5 + [0] * 3], jnp.int32)
        params = mod.init(
            jax.random.key(0), enc_ids, enc_ids[:, :1], mask
        )["params"]
        want = self._greedy_reference(mod, params, enc_ids, 4, 3, mask)
        got = np.asarray(
            smp.generate(mod, enc_ids, 4, params=params,
                         decoder_start_token_id=3, encoder_mask=mask)
        )
        np.testing.assert_array_equal(got, want)
        # The mask must reach cross-attention: the masked and unmasked
        # LOGITS of the cache-less forward must differ for the padded row
        # (token-level greedy output may coincide on a tiny random model,
        # so assert at the logits level).
        dec = jnp.full((2, 1), 3, enc_ids.dtype)
        with_mask = mod.apply({"params": params}, enc_ids, dec, mask)
        without = mod.apply({"params": params}, enc_ids, dec)
        assert not np.allclose(
            np.asarray(with_mask[1]), np.asarray(without[1])
        )


def _beam_reference(last_logits_fn, vocab, max_new, num_beams,
                    eos, length_penalty=1.0):
    """Independent (pure-python) beam search mirroring HF >= 4.38
    semantics (scores normalized by the generated length including the
    candidate token), for ONE row: ``last_logits_fn(tokens_list) ->
    np.ndarray [V]`` runs the cache-less model on prompt+tokens and
    returns the last position's logits. Returns the generated ids
    (hyp + eos + pad, length max_new)."""
    import scipy.special as sp

    beams = [(0.0, [])]
    fin = []  # (norm_score, tokens)
    stopped = False
    for step in range(max_new):
        cands = []
        for bi, (s, toks) in enumerate(beams):
            lp = sp.log_softmax(last_logits_fn(toks).astype(np.float64))
            for v in range(vocab):
                cands.append((s + lp[v], bi, v))
        cands.sort(key=lambda c: -c[0])
        new_beams = []
        for rank, (sc, bi, v) in enumerate(cands[: 2 * num_beams]):
            if eos is not None and v == eos:
                if rank < num_beams and not stopped:
                    fin.append(
                        (sc / (step + 1) ** length_penalty, beams[bi][1])
                    )
                    fin = sorted(fin, key=lambda f: -f[0])[:num_beams]
            elif len(new_beams) < num_beams:
                new_beams.append((sc, beams[bi][1] + [v]))
        if eos is not None and len(fin) >= num_beams:
            stopped = True
        beams = new_beams
    if not stopped:
        for s, toks in beams:
            fin.append((s / max_new ** length_penalty, toks))
        fin = sorted(fin, key=lambda f: -f[0])[:num_beams]
    toks = fin[0][1]
    out = list(toks)
    if eos is not None and len(out) < max_new:
        out.append(eos)
    out += [0] * (max_new - len(out))
    return np.asarray(out)


class TestBeamSearch:
    def test_beam1_without_eos_equals_greedy(self):
        smp.init({})
        mod = _zoo("rotary")
        ids = jax.random.randint(jax.random.key(30), (2, 6), 0, 97)
        params = mod.init(jax.random.key(0), ids)["params"]
        greedy = np.asarray(smp.generate(mod, ids, 5, params=params))
        beam = np.asarray(
            smp.generate(mod, ids, 5, params=params, num_beams=1)
        )
        np.testing.assert_array_equal(beam, greedy)

    @pytest.mark.parametrize("eos_mode", ["none", "forced"])
    def test_matches_python_reference(self, eos_mode):
        smp.init({})
        vocab = 23
        mod = _zoo("learned", vocab_size=vocab, d_model=32)
        ids = jax.random.randint(jax.random.key(31), (2, 5), 0, vocab)
        params = mod.init(jax.random.key(0), ids)["params"]
        # "forced": pick an id that actually appears among early beam
        # tokens so the finished-hypothesis path is exercised.
        if eos_mode == "none":
            eos = None
        else:
            probe = np.asarray(smp.generate(mod, ids, 3, params=params))
            eos = int(probe[0, 6])
        got = np.asarray(
            smp.generate(mod, ids, 6, params=params, num_beams=3,
                         eos_token_id=eos, pad_token_id=0)
        )
        for row in range(2):
            def last_logits(toks, _row=row):
                seq = jnp.asarray(
                    np.concatenate([np.asarray(ids[_row]), toks])
                    .astype(np.int32)
                )[None]
                return np.asarray(
                    mod.apply({"params": params}, seq)[0, -1]
                ).astype(np.float64)

            want = _beam_reference(last_logits, vocab, 6, 3, eos)
            np.testing.assert_array_equal(got[row, 5:], want)

    def test_seq2seq_beam_runs_and_improves_score(self):
        # Beam-3 hypothesis log-prob must be >= greedy's (same model, same
        # scoring) — the defining property of beam search.
        smp.init({})
        mod = TestSeq2SeqGreedyParity._enc_dec(t5_compat=True)
        enc = jax.random.randint(jax.random.key(32), (2, 7), 0, 89)
        params = mod.init(jax.random.key(0), enc, enc[:, :1])["params"]
        greedy = np.asarray(
            smp.generate(mod, enc, 5, params=params,
                         decoder_start_token_id=3)
        )
        beam = np.asarray(
            smp.generate(mod, enc, 5, params=params, num_beams=4,
                         decoder_start_token_id=3)
        )
        assert beam.shape == greedy.shape

        def seq_logprob(dec_rows):
            total = np.zeros(dec_rows.shape[0])
            for t in range(1, dec_rows.shape[1]):
                logits = mod.apply(
                    {"params": params}, enc,
                    jnp.asarray(dec_rows[:, :t].astype(np.int32)),
                )
                lp = jax.nn.log_softmax(
                    logits[:, -1].astype(jnp.float32), -1
                )
                total += np.asarray(
                    jnp.take_along_axis(
                        lp, jnp.asarray(dec_rows[:, t, None]), 1
                    )[:, 0]
                )
            return total

        assert (seq_logprob(beam) >= seq_logprob(greedy) - 1e-5).all()

    def test_num_return_sequences(self):
        smp.init({})
        mod = _zoo("learned")
        ids = jax.random.randint(jax.random.key(36), (2, 5), 0, 97)
        params = mod.init(jax.random.key(0), ids)["params"]
        one = np.asarray(
            smp.generate(mod, ids, 4, params=params, num_beams=3)
        )
        three = np.asarray(
            smp.generate(mod, ids, 4, params=params, num_beams=3,
                         num_return_sequences=3)
        )
        assert three.shape == (2, 3, 9)
        np.testing.assert_array_equal(three[:, 0], one)
        with pytest.raises(SMPValidationError):
            smp.generate(mod, ids, 4, params=params, num_beams=2,
                         num_return_sequences=3)

    def test_seq2seq_num_return_sequences(self):
        smp.init({})
        mod = TestSeq2SeqGreedyParity._enc_dec(t5_compat=True)
        enc = jax.random.randint(jax.random.key(37), (2, 7), 0, 89)
        params = mod.init(jax.random.key(0), enc, enc[:, :1])["params"]
        one = np.asarray(
            smp.generate(mod, enc, 4, params=params, num_beams=3,
                         decoder_start_token_id=3)
        )
        three = np.asarray(
            smp.generate(mod, enc, 4, params=params, num_beams=3,
                         decoder_start_token_id=3, num_return_sequences=3)
        )
        assert three.shape == (2, 3, 5)
        np.testing.assert_array_equal(three[:, 0], one)
        assert (three[:, :, 0] == 3).all()  # start token on every rank

    def test_beam_rejects_sampling(self):
        smp.init({})
        mod = _zoo("learned")
        ids = jnp.zeros((1, 4), jnp.int32)
        with pytest.raises(SMPValidationError):
            smp.generate(mod, ids, 2, params={}, num_beams=2,
                         temperature=0.5, rng=jax.random.key(0))


class TestHFBeamParity:
    def test_gpt2_matches_hf_beams(self):
        transformers = pytest.importorskip("transformers")
        torch = pytest.importorskip("torch")
        from tests.test_huggingface import _hf_model, _tiny_configs

        hf = _hf_model("gpt2", _tiny_configs()["gpt2"])
        smp.init({})
        model = smp.from_hf(hf, deterministic=True)
        ids = jax.random.randint(jax.random.key(33), (2, 5), 0, 64)
        with torch.no_grad():
            t_ids = torch.tensor(np.asarray(ids))
            want = hf.generate(
                t_ids, attention_mask=torch.ones_like(t_ids),
                max_new_tokens=4, num_beams=3, do_sample=False,
                early_stopping=True, pad_token_id=0,
            ).numpy()
        got = np.asarray(model.generate(ids, 4, num_beams=3))
        L = want.shape[1]
        np.testing.assert_array_equal(got[:, :L], want)
        assert (got[:, L:] == 0).all()

    def test_gpt2_matches_hf_beams_with_eos_and_length_penalty(self):
        # In-vocab EOS + length_penalty != 1 makes the normalization and
        # the finished-vs-live ranking actually decide the output.
        transformers = pytest.importorskip("transformers")
        torch = pytest.importorskip("torch")
        from tests.test_huggingface import _hf_model, _tiny_configs

        hf = _hf_model("gpt2", _tiny_configs()["gpt2"])
        smp.init({})
        model = smp.from_hf(hf, deterministic=True)
        ids = jax.random.randint(jax.random.key(35), (3, 5), 0, 64)
        probe = np.asarray(model.generate(ids, 2))
        eos = int(probe[0, 6])  # a token beams will actually propose
        with torch.no_grad():
            t_ids = torch.tensor(np.asarray(ids))
            want = hf.generate(
                t_ids, attention_mask=torch.ones_like(t_ids),
                max_new_tokens=6, num_beams=3, do_sample=False,
                early_stopping=True, pad_token_id=0, eos_token_id=eos,
                length_penalty=2.0,
            ).numpy()
        got = np.asarray(
            model.generate(ids, 6, num_beams=3, eos_token_id=eos,
                           length_penalty=2.0)
        )
        L = want.shape[1]
        np.testing.assert_array_equal(got[:, :L], want)
        assert (got[:, L:] == 0).all()

    def test_t5_matches_hf_beams(self):
        transformers = pytest.importorskip("transformers")
        torch = pytest.importorskip("torch")

        config = transformers.T5Config(
            d_model=32, d_ff=64, d_kv=8, num_layers=2, num_heads=4,
            vocab_size=96, dropout_rate=0.0, decoder_start_token_id=0,
        )
        torch.manual_seed(0)
        hf = transformers.T5ForConditionalGeneration(config)
        hf.eval()
        smp.init({})
        model = smp.from_hf(hf, deterministic=True)
        ids = jax.random.randint(jax.random.key(34), (2, 6), 2, 96)
        with torch.no_grad():
            want = hf.generate(
                torch.tensor(np.asarray(ids)),
                max_new_tokens=5, num_beams=3, do_sample=False,
                early_stopping=True,
            ).numpy()
        got = np.asarray(
            model.generate(ids, 5, num_beams=3, eos_token_id=1,
                           decoder_start_token_id=0)
        )
        L = want.shape[1]
        np.testing.assert_array_equal(got[:, :L], want)
        assert (got[:, L:] == 0).all()


class TestPaddedPrompts:
    """Left-padded ragged prompts: the gold invariant is that a padded
    batch row generates exactly what the unpadded prompt generates
    alone (positions shift per row; padded columns never attend)."""

    @staticmethod
    def _head(**kw):
        kw.setdefault("num_layers", 2)
        kw.setdefault("num_attention_heads", 4)
        kw.setdefault("attention_head_size", 8)
        kw.setdefault("hidden_size", 32)
        kw.setdefault("intermediate_size", 64)
        kw.setdefault("vocab_size", 97)
        kw.setdefault("num_positions", 64)
        kw.setdefault("causal_mask_size", 64)
        kw.setdefault("attention_dropout_prob", 0.0)
        kw.setdefault("hidden_dropout_prob", 0.0)
        kw.setdefault("embedding_dropout_prob", 0.0)
        kw.setdefault("deterministic", True)
        return DistributedTransformerLMHead(**kw)

    @pytest.mark.parametrize(
        "kw",
        [
            {},  # learned positions
            {   # NeoX rotary (per-row rotary offsets)
                "use_positional_embedding": False,
                "rotary_dim": 8,
                "gpt_neox_type_rotary": True,
                "pre_layernorm": True,
                "post_layernorm": False,
                "final_layernorm": True,
            },
        ],
        ids=["learned_pos", "rotary"],
    )
    def test_padded_row_equals_unpadded(self, kw):
        smp.init({})
        mod = self._head(**kw)
        full = jax.random.randint(jax.random.key(40), (2, 6), 1, 97)
        # Row 1's true prompt is its last 4 tokens; left-pad with zeros.
        padded = full.at[1, :2].set(0)
        mask = jnp.asarray([[1] * 6, [0, 0, 1, 1, 1, 1]], jnp.int32)
        params = mod.init(jax.random.key(0), padded)["params"]
        got = np.asarray(
            smp.generate(mod, padded, 5, params=params,
                         attention_mask=mask)
        )
        single = np.asarray(
            smp.generate(mod, full[1:2, 2:], 5, params=params)
        )
        np.testing.assert_array_equal(got[1, 6:], single[0, 4:])
        # Unpadded row must match the no-mask path too.
        plain = np.asarray(smp.generate(mod, full[0:1], 5, params=params))
        np.testing.assert_array_equal(got[0], plain[0])

    def test_beams_with_padded_prompts(self):
        smp.init({})
        mod = self._head()
        full = jax.random.randint(jax.random.key(41), (2, 6), 1, 97)
        padded = full.at[1, :2].set(0)
        mask = jnp.asarray([[1] * 6, [0, 0, 1, 1, 1, 1]], jnp.int32)
        params = mod.init(jax.random.key(0), padded)["params"]
        got = np.asarray(
            smp.generate(mod, padded, 4, params=params,
                         attention_mask=mask, num_beams=3)
        )
        single = np.asarray(
            smp.generate(mod, full[1:2, 2:], 4, params=params, num_beams=3)
        )
        np.testing.assert_array_equal(got[1, 6:], single[0, 4:])

    def test_hf_gpt2_left_padded_parity(self):
        transformers = pytest.importorskip("transformers")
        torch = pytest.importorskip("torch")
        from tests.test_huggingface import _hf_model, _tiny_configs

        hf = _hf_model("gpt2", _tiny_configs()["gpt2"])
        smp.init({})
        model = smp.from_hf(hf, deterministic=True)
        ids = jax.random.randint(jax.random.key(42), (2, 6), 1, 64)
        ids = ids.at[1, :3].set(0)
        mask = jnp.asarray([[1] * 6, [0, 0, 0, 1, 1, 1]], jnp.int32)
        with torch.no_grad():
            want = hf.generate(
                torch.tensor(np.asarray(ids)),
                attention_mask=torch.tensor(np.asarray(mask)),
                max_new_tokens=5, do_sample=False, pad_token_id=0,
            ).numpy()
        got = np.asarray(model.generate(ids, 5, attention_mask=mask))
        np.testing.assert_array_equal(got, want)

    def test_zoo_family_rejects_mask(self):
        smp.init({})
        mod = _zoo("learned")
        ids = jnp.zeros((1, 4), jnp.int32)
        with pytest.raises(SMPValidationError, match="attention_mask"):
            smp.generate(mod, ids, 2, params={},
                         attention_mask=jnp.ones((1, 4), jnp.int32))


class TestHFGreedyParity:
    """The strongest end-to-end check: a translated HF causal LM must
    greedily continue prompts exactly like HF's own ``generate``."""

    @pytest.mark.parametrize("name", ["gpt2", "gptj", "gptneox"])
    def test_matches_hf_generate(self, name):
        transformers = pytest.importorskip("transformers")
        torch = pytest.importorskip("torch")
        from tests.test_huggingface import _hf_model, _tiny_configs

        config = _tiny_configs()[name]
        hf = _hf_model(name, config)
        smp.init({})
        model = smp.from_hf(hf, deterministic=True)
        ids = jax.random.randint(jax.random.key(11), (2, 6), 0, 64)
        with torch.no_grad():
            t_ids = torch.tensor(np.asarray(ids))
            want = hf.generate(
                t_ids,
                # Explicit all-ones mask: HF otherwise infers one from
                # pad_token_id and random prompts may contain that id.
                attention_mask=torch.ones_like(t_ids),
                max_new_tokens=5,
                do_sample=False,
                pad_token_id=0,
            ).numpy()
        got = np.asarray(model.generate(ids, 5))
        np.testing.assert_array_equal(got, want)

    def test_t5_matches_hf_generate(self):
        transformers = pytest.importorskip("transformers")
        torch = pytest.importorskip("torch")

        config = transformers.T5Config(
            d_model=32, d_ff=64, d_kv=8, num_layers=2, num_heads=4,
            vocab_size=96, dropout_rate=0.0, decoder_start_token_id=0,
        )
        torch.manual_seed(0)
        hf = transformers.T5ForConditionalGeneration(config)
        hf.eval()
        smp.init({})
        model = smp.from_hf(hf, deterministic=True)
        ids = jax.random.randint(jax.random.key(12), (2, 7), 2, 96)
        with torch.no_grad():
            want = hf.generate(
                torch.tensor(np.asarray(ids)),
                max_new_tokens=5,
                do_sample=False,
                # Tiny random models emit EOS (id 1) arbitrarily; disable
                # early stop so both sides generate all 5 tokens.
                eos_token_id=None,
            ).numpy()
        got = np.asarray(
            model.generate(ids, 5, decoder_start_token_id=0)
        )
        np.testing.assert_array_equal(got, want)


class TestDecodeLengthBuckets:
    """ISSUE 14 satellite: SMP_SHAPE_BUCKETS "seq" sizes bucket
    (prompt-len, max-new-tokens) so ragged serving-style prompts reuse
    one cached program instead of churning the _COMPILED LRU."""

    @staticmethod
    def _head():
        return DistributedTransformerLMHead(
            num_layers=2, num_attention_heads=4, attention_head_size=8,
            hidden_size=32, intermediate_size=64, vocab_size=97,
            num_positions=64, causal_mask_size=64,
            attention_dropout_prob=0.0, hidden_dropout_prob=0.0,
            embedding_dropout_prob=0.0, deterministic=True,
        )

    def test_ragged_prompts_share_one_program(self, monkeypatch):
        from smdistributed_modelparallel_tpu.generation import _COMPILED

        smp.init({})
        mod = self._head()
        ids5 = jax.random.randint(jax.random.key(60), (2, 5), 1, 97)
        ids7 = jax.random.randint(jax.random.key(61), (2, 7), 1, 97)
        params = mod.init(jax.random.key(0), ids5)["params"]
        ref5 = np.asarray(smp.generate(mod, ids5, 3, params=params))
        ref7 = np.asarray(smp.generate(mod, ids7, 5, params=params))

        monkeypatch.setenv("SMP_SHAPE_BUCKETS", "seq:8,16")
        got5 = np.asarray(smp.generate(mod, ids5, 3, params=params))
        entries_after_first = len(_COMPILED)
        got7 = np.asarray(smp.generate(mod, ids7, 5, params=params))
        # Both (5, +3) and (7, +5) land in the (8, +8) bucket: the second
        # call HITS the first call's compiled entry.
        assert len(_COMPILED) == entries_after_first
        # Bucketing is output-invariant (greedy): callers see exactly the
        # (prompt, max_new) they asked for.
        np.testing.assert_array_equal(got5, ref5)
        np.testing.assert_array_equal(got7, ref7)

    def test_zoo_family_buckets_decode_length_only(self, monkeypatch):
        # No attention_mask support: the prompt stays exact, only
        # max_new_tokens rounds up (and the extra steps are sliced off).
        smp.init({})
        mod = _zoo("rotary")
        ids = jax.random.randint(jax.random.key(62), (2, 5), 0, 97)
        params = mod.init(jax.random.key(0), ids)["params"]
        ref = np.asarray(smp.generate(mod, ids, 3, params=params))
        monkeypatch.setenv("SMP_SHAPE_BUCKETS", "seq:8,16")
        got = np.asarray(smp.generate(mod, ids, 3, params=params))
        np.testing.assert_array_equal(got, ref)
        assert got.shape == (2, 8)

    def test_eos_rows_and_overflow(self, monkeypatch):
        smp.init({})
        mod = self._head()
        ids = jax.random.randint(jax.random.key(63), (2, 6), 1, 97)
        params = mod.init(jax.random.key(0), ids)["params"]
        probe = np.asarray(smp.generate(mod, ids, 4, params=params))
        eos = int(probe[0, 6])
        ref = np.asarray(smp.generate(mod, ids, 4, params=params,
                                      eos_token_id=eos, pad_token_id=0))
        ref_big = np.asarray(smp.generate(mod, ids, 12, params=params))
        monkeypatch.setenv("SMP_SHAPE_BUCKETS", "seq:8")
        # EOS-frozen rows emit pad through the bucketed extra steps —
        # sliced off, identical output.
        got = np.asarray(smp.generate(mod, ids, 4, params=params,
                                      eos_token_id=eos, pad_token_id=0))
        np.testing.assert_array_equal(got, ref)
        # max_new beyond every bucket: decode length compiles exact,
        # identical output.
        got_big = np.asarray(smp.generate(mod, ids, 12, params=params))
        np.testing.assert_array_equal(got_big, ref_big)

    def test_bucket_never_exceeds_position_limit(self, monkeypatch):
        # (6, +9) fits a 16-position model exactly; both bucket
        # components would push past the limit and must be skipped.
        smp.init({})
        mod = _zoo("rotary", max_len=16)
        ids = jax.random.randint(jax.random.key(64), (1, 6), 0, 97)
        params = mod.init(jax.random.key(0), ids)["params"]
        ref = np.asarray(smp.generate(mod, ids, 9, params=params))
        monkeypatch.setenv("SMP_SHAPE_BUCKETS", "seq:8,16")
        got = np.asarray(smp.generate(mod, ids, 9, params=params))
        np.testing.assert_array_equal(got, ref)
        assert got.shape == (1, 15)


class TestHalfPrecision:
    def test_bf16_config_casts_decode_params(self):
        """Under a bf16 config, generation runs the half-cast forward
        (training-step parity): the KV caches must be bf16 and the
        output must equal a manual bf16 cache-less greedy loop."""
        smp.init({"bf16": True})
        mod = _zoo("rotary")
        ids = jax.random.randint(jax.random.key(50), (2, 6), 0, 97)
        params = mod.init(jax.random.key(0), ids)["params"]
        out = np.asarray(smp.generate(mod, ids, 4, params=params))

        bp = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.bfloat16)
            if jnp.issubdtype(p.dtype, jnp.floating) else p, params)
        cur = ids
        for _ in range(4):
            nxt = jnp.argmax(
                mod.apply({"params": bp}, cur)[:, -1].astype(jnp.float32),
                -1,
            )
            cur = jnp.concatenate([cur, nxt[:, None].astype(cur.dtype)], 1)
        np.testing.assert_array_equal(out, np.asarray(cur))

        # The cache itself must be half precision (HBM footprint parity).
        dm = mod.clone(decode=True, decode_cache_len=10, deterministic=True)
        from smdistributed_modelparallel_tpu.generation import _half_cast

        _, mut = dm.apply(
            {"params": _half_cast(params, jnp.bfloat16)}, ids,
            mutable=["cache"],
        )
        leaves = jax.tree_util.tree_leaves(mut["cache"])
        float_leaves = [
            l for l in leaves if jnp.issubdtype(l.dtype, jnp.floating)
        ]
        assert float_leaves
        assert all(l.dtype == jnp.bfloat16 for l in float_leaves)
