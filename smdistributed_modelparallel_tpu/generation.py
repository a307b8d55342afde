"""Autoregressive generation with KV caches — ``smp.generate``.

TPU extension (no reference counterpart): the reference
(``smdistributed.modelparallel``) is a training library; its users sample
from fine-tuned models by exporting to HF. A complete switch-over needs
generation in-framework: this module drives the attention layers' decode
mode (``nn/utils.DecodeKVCache``) as one compiled program — a prefill pass
over the prompt (full flash-attention fast path) followed by a
``lax.scan`` of single-token decode steps, with greedy / temperature /
top-k / top-p sampling and per-row EOS early-stop masking.

Design notes (TPU-first):
- The whole generation (prefill + all decode steps) is ONE jitted
  program: no per-token host round trips, and XLA keeps the cache update
  (``dynamic_update_slice`` on a scan carry) in place.
- Under tensor parallelism nothing changes here: the decode forward runs
  the same TP-sharded layers; GSPMD shards the [B, C, H, hd] caches over
  the head axis exactly like the activations they buffer.
- Under pipeline parallelism the decode path does not run the pipeline
  schedule: a ``DistributedModel``'s pp-stage-sharded layer stacks are
  regathered onto the full mesh (``model.regather_for_decode``, cached
  until the params change) and decode runs as a plain tp/dp forward —
  train at pp x tp, then sample, without a topology change.
"""

import collections

import jax
import jax.numpy as jnp
import numpy as np

from smdistributed_modelparallel_tpu.backend.state import state
from smdistributed_modelparallel_tpu.utils.exceptions import SMPValidationError

# Compiled-generator cache: flax modules are frozen dataclasses (hashable
# when their fields are), so (module, shapes, sampling config) keys a
# ready program across repeated generate() calls. LRU-bounded: serving
# ragged prompt shapes would otherwise leak one compiled program per
# (B, T, max_new_tokens, ...) combination for the process lifetime —
# callers with more than _COMPILED_CAP live shapes should pad prompts to
# a fixed set of bucket shapes.
_COMPILED_CAP = 32
_COMPILED = collections.OrderedDict()


def _top_k_filter(logits, top_k):
    top_k = min(top_k, logits.shape[-1])  # HF convention: clamp to vocab
    kth = jnp.sort(logits, axis=-1)[..., -top_k][..., None]
    return jnp.where(logits >= kth, logits, -jnp.inf)


def _top_p_filter(logits, top_p):
    sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # Keep tokens whose cumulative probability BEFORE them is < top_p
    # (always keeps the most likely token).
    keep = (cum - probs) < top_p
    thresh = jnp.min(
        jnp.where(keep, sorted_desc, jnp.inf), axis=-1, keepdims=True
    )
    return jnp.where(logits >= thresh, logits, -jnp.inf)


def _make_sampler(temperature, top_k, top_p):
    if temperature == 0.0:
        return lambda logits, rng: jnp.argmax(logits, axis=-1)

    def sample(logits, rng):
        logits = logits / temperature
        if top_k is not None:
            logits = _top_k_filter(logits, top_k)
        if top_p is not None:
            logits = _top_p_filter(logits, top_p)
        return jax.random.categorical(rng, logits, axis=-1)

    return sample


def _decode_clone(module, cache_len):
    try:
        return module.clone(
            decode=True, decode_cache_len=cache_len, deterministic=True
        )
    except TypeError as e:
        raise SMPValidationError(
            f"{type(module).__name__} does not support KV-cache decoding "
            "(needs decode/decode_cache_len/deterministic fields — the "
            "TransformerLM zoo family and smp.nn DistributedTransformerLMHead "
            "do)."
        ) from e


def _decode_loop(apply_step, prefill_out, max_new_tokens,
                 sampler, eos_token_id, pad_token_id, rng):
    """Shared sample-feed-sample loop after a prefill: returns the
    [B, max_new_tokens] generated ids."""
    logits, cache = prefill_out
    B = logits.shape[0]
    rngs = jax.random.split(rng, max_new_tokens)
    tok = sampler(logits[:, -1].astype(jnp.float32), rngs[0])
    done = jnp.zeros((B,), bool)
    if eos_token_id is not None:
        done = tok == eos_token_id

    def body(carry, step_rng):
        cache, tok, done = carry
        logits, cache = apply_step(cache, tok[:, None])
        nxt = sampler(logits[:, -1].astype(jnp.float32), step_rng)
        if eos_token_id is not None:
            nxt = jnp.where(done, pad_token_id, nxt)
            new_done = done | (nxt == eos_token_id)
        else:
            new_done = done
        return (cache, nxt, new_done), nxt

    (_, _, _), rest = jax.lax.scan(body, (cache, tok, done), rngs[1:])
    return jnp.concatenate([tok[:, None], rest.transpose(1, 0)], axis=1)


def _half_cast(params, half):
    """Match the training step's compute dtype: under bf16/fp16 configs
    the decode forward runs on half-precision params, so generation
    throughput and numerics track training (shared predicate:
    nn/utils.half_cast). Under ``SMP_DECODE_WEIGHTS=int8`` the params
    first round-trip through the serving path's per-channel int8 grid
    (fake-quant — value-identical to store-int8 + dequant), so
    ``smp.generate`` and the serving engine emit the same tokens under
    the same knob."""
    from smdistributed_modelparallel_tpu import quant
    from smdistributed_modelparallel_tpu.nn.utils import half_cast

    if quant.decode_weights_mode() == "int8":
        params = quant.fake_quant_decode_params(params)
    return half_cast(params, half)


def _step_masks(mask, max_new_tokens):
    """(prefill [B,1,1,T], step [B,1,1,C]) boolean masks from a [B, T]
    LEFT-padded prompt mask; generated columns are always kept."""
    mask = mask.astype(bool)
    B = mask.shape[0]
    step = jnp.concatenate(
        [mask, jnp.ones((B, max_new_tokens), bool)], axis=1
    )
    return mask[:, None, None, :], step[:, None, None, :]


def _build_generator(decode_mod, max_new_tokens, sampler, eos_token_id,
                     pad_token_id, half=None):
    """Decoder-only generation body:
    (params, ids, mask | None, rng) -> [B, total] ids."""

    def run(params, ids, mask, rng):
        params = _half_cast(params, half)
        pre_kw, step_kw = {}, {}
        if mask is not None:
            pre_mask, step_mask = _step_masks(mask, max_new_tokens)
            pre_kw = {"attention_mask": pre_mask}
            step_kw = {"attention_mask": step_mask}
        logits, mut = decode_mod.apply(
            {"params": params}, ids, mutable=["cache"], **pre_kw
        )

        def apply_step(cache, tok):
            logits, mut = decode_mod.apply(
                {"params": params, "cache": cache}, tok,
                mutable=["cache"], **step_kw,
            )
            return logits, mut["cache"]

        new_tokens = _decode_loop(
            apply_step, (logits, mut["cache"]), max_new_tokens,
            sampler, eos_token_id, pad_token_id, rng,
        ).astype(ids.dtype)
        return jnp.concatenate([ids, new_tokens], axis=1)

    return run


def _build_seq2seq_generator(decode_mod, max_new_tokens, sampler,
                             eos_token_id, pad_token_id,
                             decoder_start_token_id, half=None):
    """Seq2seq generation body: encode once, KV-cached decoder steps.
    (params, encoder_ids, encoder_mask, rng) -> [B, 1 + max_new] decoder
    ids (start token first, HF ``generate`` convention)."""

    def run(params, enc_ids, enc_mask, rng):
        params = _half_cast(params, half)
        B = enc_ids.shape[0]
        h_e, _ = decode_mod.apply(
            {"params": params}, enc_ids, enc_mask,
            method="encode", mutable=["cache"],
        )
        start = jnp.full((B, 1), decoder_start_token_id, enc_ids.dtype)
        logits, mut = decode_mod.apply(
            {"params": params}, start, h_e, enc_mask,
            method="decode_step", mutable=["cache"],
        )

        def apply_step(cache, tok):
            logits, mut = decode_mod.apply(
                {"params": params, "cache": cache}, tok, h_e, enc_mask,
                method="decode_step", mutable=["cache"],
            )
            return logits, mut["cache"]

        new_tokens = _decode_loop(
            apply_step, (logits, mut["cache"]), max_new_tokens,
            sampler, eos_token_id, pad_token_id, rng,
        ).astype(enc_ids.dtype)
        return jnp.concatenate([start, new_tokens], axis=1)

    return run


# ----------------------------------------------------------------------
# Beam search (greedy beams, HF-compatible scoring: length_penalty
# normalization at EOS time, early_stopping=True semantics).
# ----------------------------------------------------------------------

# Plain python float: a module-level jnp array would initialize the
# accelerator backend at import time.
_NEG = -1e9


def _reorder_beam_cache(cache, parent_flat):
    """Gather the growing self-attention caches along the folded [B*N]
    beam axis. Under ``nn.scan`` the per-layer caches stack on a leading
    layer axis — ``cached_key``/``cached_value`` are [L, B*N, C, H, hd],
    so the gather is on axis 1. ``cross_kv`` (encoder K/V) is identical
    across the beams of a row and index counters are scalars; both pass
    through untouched."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
    out = []
    for path, leaf in flat:
        name = getattr(path[-1], "key", None)
        if name in ("cached_key", "cached_value"):
            out.append(jnp.take(leaf, parent_flat, axis=1))
        else:
            out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def _build_beam_generator(decode_mod, max_new_tokens, num_beams,
                          eos_token_id, pad_token_id, length_penalty,
                          seq2seq, decoder_start_token_id,
                          num_return_sequences=1, half=None):
    """Compiled beam-search body. Beams fold into the batch axis (the
    model sees [B*N, ...]); each step takes the top-2N candidates over
    [N x vocab], routes EOS candidates into a best-N finished store
    (scores normalized by HF's ``cur_len ** length_penalty``), continues
    the top-N non-EOS beams, and gathers the KV caches to the surviving
    parents. Everything — prefill, all steps, finalize — is one program.
    """
    N = num_beams

    def select(logprobs, cache, beam_scores, seqs, fin, stopped, step):
        B = beam_scores.shape[0]
        V = logprobs.shape[-1]
        fin_scores, fin_seqs, fin_len = fin
        cand = beam_scores[:, :, None] + logprobs.reshape(B, N, V)
        s2, i2 = jax.lax.top_k(cand.reshape(B, N * V), 2 * N)
        tok2 = i2 % V
        par2 = i2 // V
        rows = jnp.arange(B)[:, None]
        if eos_token_id is not None:
            eos2 = tok2 == eos_token_id
            # Finished store: merge this step's EOS candidates (parent
            # sequence WITHOUT the eos token; only EOS ranked within the
            # top N counts — HF drops worse-than-top-N EOS) with the kept
            # hypotheses; keep the best N overall. Scores normalize by
            # the GENERATED length including the eos (transformers >=
            # 4.38: ``cur_len + 1 - decoder_prompt_len``); frozen rows
            # (early_stopping reached) contribute nothing.
            norm = s2 / jnp.float32(step + 1) ** length_penalty
            in_top_n = jnp.arange(2 * N)[None, :] < N
            cand_fin = jnp.where(
                eos2 & in_top_n & ~stopped[:, None], norm, _NEG
            )
            all_scores = jnp.concatenate([fin_scores, cand_fin], axis=1)
            all_seqs = jnp.concatenate([fin_seqs, seqs[rows, par2]], axis=1)
            all_len = jnp.concatenate(
                [fin_len, jnp.full((B, 2 * N), step, jnp.int32)], axis=1
            )
            fin_scores, fidx = jax.lax.top_k(all_scores, N)
            fin_seqs = jnp.take_along_axis(all_seqs, fidx[:, :, None], 1)
            fin_len = jnp.take_along_axis(all_len, fidx, 1)
            stopped = stopped | (
                jnp.sum(fin_scores > _NEG / 2, axis=1) >= N
            )
            s2 = jnp.where(eos2, _NEG, s2)
        new_scores, pos = jax.lax.top_k(s2, N)
        tokN = jnp.take_along_axis(tok2, pos, 1)
        parN = jnp.take_along_axis(par2, pos, 1)
        new_seqs = seqs[rows, parN]
        new_seqs = jax.lax.dynamic_update_slice_in_dim(
            new_seqs, tokN[:, :, None], step, axis=2
        )
        parent_flat = (rows * N + parN).reshape(-1)
        cache = _reorder_beam_cache(cache, parent_flat)
        return (cache, tokN.reshape(-1), new_scores, new_seqs,
                (fin_scores, fin_seqs, fin_len), stopped)

    def finish(beam_scores, seqs, fin, stopped, out_dtype):
        """HF finalize: non-stopped rows also offer their live beams
        (normalized by the full generated length — the last-iteration
        max-length merge in transformers); best hypothesis wins; output
        is hyp + eos + pad."""
        B = beam_scores.shape[0]
        fin_scores, fin_seqs, fin_len = fin
        final_norm = beam_scores / (
            jnp.float32(max_new_tokens) ** length_penalty
        )
        live = jnp.where(~stopped[:, None], final_norm, _NEG)
        if eos_token_id is None:
            live = final_norm
        all_scores = jnp.concatenate([fin_scores, live], axis=1)
        all_seqs = jnp.concatenate([fin_seqs, seqs], axis=1)
        all_len = jnp.concatenate(
            [fin_len,
             jnp.full((B, N), max_new_tokens, jnp.int32)], axis=1
        )
        R = num_return_sequences
        _, best = jax.lax.top_k(all_scores, R)              # [B, R]
        seq = jnp.take_along_axis(all_seqs, best[:, :, None], 1)  # [B,R,L]
        length = jnp.take_along_axis(all_len, best, 1)       # [B, R]
        cols = jnp.arange(max_new_tokens)[None, None, :]
        eos_fill = eos_token_id if eos_token_id is not None else pad_token_id
        out = jnp.where(
            cols < length[:, :, None], seq,
            jnp.where(cols == length[:, :, None], eos_fill, pad_token_id),
        ).astype(out_dtype)
        return out[:, 0] if R == 1 else out

    def loop(cache, first_logits, seqs0, apply_step, B, out_dtype):
        logprobs = jax.nn.log_softmax(
            first_logits[:, -1].astype(jnp.float32), axis=-1
        ).reshape(B, N, -1)
        # Step 0: the N beams of a row are identical clones — only beam 0
        # may propose candidates (HF seeds beam scores [0, -inf, ...]).
        beam_scores = jnp.full((B, N), _NEG).at[:, 0].set(0.0)
        fin = (
            jnp.full((B, N), _NEG),
            jnp.zeros((B, N, max_new_tokens), jnp.int32),
            jnp.zeros((B, N), jnp.int32),
        )
        stopped = jnp.zeros((B,), bool)
        cache, tok, beam_scores, seqs, fin, stopped = select(
            logprobs.reshape(B * N, -1), cache, beam_scores, seqs0, fin,
            stopped, 0,
        )

        def body(carry, step):
            cache, tok, beam_scores, seqs, fin, stopped = carry
            logits, cache = apply_step(cache, tok[:, None])
            logprobs = jax.nn.log_softmax(
                logits[:, -1].astype(jnp.float32), axis=-1
            )
            return select(logprobs, cache, beam_scores, seqs, fin,
                          stopped, step), None

        (cache, tok, beam_scores, seqs, fin, stopped), _ = jax.lax.scan(
            body,
            (cache, tok, beam_scores, seqs, fin, stopped),
            jnp.arange(1, max_new_tokens),
        )
        return finish(beam_scores, seqs, fin, stopped, out_dtype)

    if seq2seq:
        def run(params, enc_ids, enc_mask, rng):
            params = _half_cast(params, half)
            B, S = enc_ids.shape
            h_e = decode_mod.apply(
                {"params": params}, enc_ids, enc_mask,
                method="encode", mutable=["cache"],
            )[0]
            h_e = jnp.repeat(h_e, N, axis=0)
            enc_mask_t = (
                None if enc_mask is None else jnp.repeat(enc_mask, N, axis=0)
            )
            start = jnp.full((B * N, 1), decoder_start_token_id,
                             enc_ids.dtype)
            logits, mut = decode_mod.apply(
                {"params": params}, start, h_e, enc_mask_t,
                method="decode_step", mutable=["cache"],
            )

            def apply_step(cache, tok):
                logits, mut = decode_mod.apply(
                    {"params": params, "cache": cache}, tok, h_e,
                    enc_mask_t, method="decode_step", mutable=["cache"],
                )
                return logits, mut["cache"]

            seqs0 = jnp.zeros((B, N, max_new_tokens), jnp.int32)
            gen = loop(mut["cache"], logits, seqs0, apply_step, B,
                       enc_ids.dtype)
            if num_return_sequences > 1:
                s = jnp.broadcast_to(
                    start[::N][:, None],
                    (B, num_return_sequences, 1),
                )
                return jnp.concatenate([s, gen], axis=2)
            return jnp.concatenate([start[::N], gen], axis=1)
    else:
        def run(params, ids, mask, rng):
            params = _half_cast(params, half)
            B, T = ids.shape
            ids_t = jnp.repeat(ids, N, axis=0)
            pre_kw, step_kw = {}, {}
            if mask is not None:
                mask_t = jnp.repeat(mask, N, axis=0)
                pre_mask, step_mask = _step_masks(mask_t, max_new_tokens)
                pre_kw = {"attention_mask": pre_mask}
                step_kw = {"attention_mask": step_mask}
            logits, mut = decode_mod.apply(
                {"params": params}, ids_t, mutable=["cache"], **pre_kw
            )

            def apply_step(cache, tok):
                logits, mut = decode_mod.apply(
                    {"params": params, "cache": cache}, tok,
                    mutable=["cache"], **step_kw,
                )
                return logits, mut["cache"]

            seqs0 = jnp.zeros((B, N, max_new_tokens), jnp.int32)
            gen = loop(mut["cache"], logits, seqs0, apply_step, B,
                       ids.dtype)
            if num_return_sequences > 1:
                idsr = jnp.broadcast_to(
                    ids[:, None], (B, num_return_sequences, T)
                )
                return jnp.concatenate([idsr, gen], axis=2)
            return jnp.concatenate([ids, gen], axis=1)

    return run


def generate(model, input_ids, max_new_tokens, *, temperature=0.0,
             top_k=None, top_p=None, eos_token_id=None, pad_token_id=0,
             rng=None, params=None, encoder_mask=None, attention_mask=None,
             decoder_start_token_id=0, num_beams=1, length_penalty=1.0,
             num_return_sequences=1):
    """Generate ``max_new_tokens`` continuation tokens for each prompt.

    Args:
      model: a ``DistributedModel`` wrapping a decode-capable LM (the
        ``TransformerLM`` zoo family, ``smp.nn.DistributedTransformerLMHead``,
        the ``EncoderDecoderLM`` seq2seq family, or an
        ``smp.from_hf``-translated causal/seq2seq LM), or such a flax
        module directly (then ``params`` is required).
      input_ids: [B, T] int prompt tokens — the ENCODER input for a
        seq2seq model. Decoder-only prompts of different true lengths
        must be LEFT-padded, with ``attention_mask`` marking real tokens;
        without a mask they are taken as unpadded.
      max_new_tokens: number of tokens to append.
      temperature: 0.0 = greedy argmax (default); > 0 samples.
      top_k / top_p: optional sampling filters (compose: k then p).
      eos_token_id: when set, rows that emit EOS are frozen and padded
        with ``pad_token_id`` for the remaining steps.
      rng: ``jax.random`` key for sampling (required when temperature > 0).
      params: parameter tree override (defaults to the model's).
      encoder_mask: seq2seq only — [B, S] encoder padding mask (1/True =
        keep), forwarded to cross-attention.
      attention_mask: decoder-only — [B, T] LEFT-padded prompt mask
        (1/True = real token). Positions shift per row by the pad count
        (HF convention) and padded columns never attend.
      decoder_start_token_id: seq2seq only — the decoder's BOS.
      num_beams: > 1 switches to beam search (greedy beams; requires
        temperature == 0). HF-compatible scoring: hypothesis scores are
        sum-logprob / (cur_len ** length_penalty), ``early_stopping=True``
        semantics (a row freezes once num_beams hypotheses finish).
      length_penalty: beam-score length normalization exponent.
      num_return_sequences: beams only — return the top R hypotheses per
        row (R <= num_beams) as a [B, R, L] array instead of [B, L].

    Pipeline parallelism: with a ``DistributedModel`` trained at pp > 1,
    generation regathers the pp-sharded layer stacks for decode
    automatically (see ``DistributedModel.regather_for_decode``); a raw
    flax module under pp needs explicit ``params``.

    Returns:
      Decoder-only: [B, T + max_new_tokens] — prompts with continuations.
      Seq2seq: [B, 1 + max_new_tokens] — start token + generated ids.
      With beams, finished rows are "hypothesis + EOS + pad" padded; with
      ``num_return_sequences`` R > 1 the shape gains a rank-R axis.
    """
    pp_active = (
        state.cfg is not None and state.cfg.pipeline_parallel_degree > 1
    )
    if pp_active and params is None and not hasattr(
        model, "regather_for_decode"
    ):
        raise SMPValidationError(
            "smp.generate under pipeline_parallel_degree > 1 needs a "
            "DistributedModel (whose pp-sharded params are regathered "
            "for decode) or explicit params=..."
        )
    if max_new_tokens < 1:
        raise SMPValidationError("max_new_tokens must be >= 1.")
    input_ids = jnp.asarray(input_ids)
    if hasattr(model, "module"):  # DistributedModel
        module = model.module
        seq2seq = hasattr(module, "encode") and hasattr(module, "decode_step")
        if params is None:
            if model.params is None:
                init_args = (
                    (input_ids, input_ids[:, :1]) if seq2seq else (input_ids,)
                )
                model._eager_init(init_args, {})
            if pp_active:
                # Decode is a plain forward (no pipeline schedule): the
                # pp-stage-sharded layer stacks regather onto the full
                # mesh, tp/ZeRO axes intact. Cached until the params
                # change, so steady-state sampling pays no re-gather.
                params = model.regather_for_decode()
            else:
                params = model.params
    else:
        module = model
        seq2seq = hasattr(module, "encode") and hasattr(module, "decode_step")
        if params is None:
            raise SMPValidationError(
                "generate(flax_module, ...) requires params=..."
            )
    if encoder_mask is not None and not seq2seq:
        raise SMPValidationError(
            "decoder-only models take attention_mask, not encoder_mask."
        )
    if attention_mask is not None:
        if seq2seq:
            raise SMPValidationError(
                "seq2seq models take encoder_mask, not attention_mask."
            )
        import inspect

        if "attention_mask" not in inspect.signature(
            type(module).__call__
        ).parameters:
            raise SMPValidationError(
                f"{type(module).__name__} does not accept attention_mask; "
                "padded-prompt generation needs the smp.nn "
                "DistributedTransformerLMHead family (incl. smp.from_hf "
                "models)."
            )
        attention_mask = jnp.asarray(attention_mask)
        if attention_mask.shape != input_ids.shape:
            raise SMPValidationError(
                f"attention_mask shape {attention_mask.shape} != prompt "
                f"shape {input_ids.shape}."
            )
        # Eager left-paddedness check (the mask is a concrete host array
        # here): a right-padded mask would silently sample the first
        # continuation from a masked pad position's logits.
        m = np.asarray(attention_mask).astype(bool)
        if not ((m[:, 1:] >= m[:, :-1]).all() and m[:, -1].all()):
            raise SMPValidationError(
                "attention_mask must be LEFT-padded (rows 0..0 1..1 with "
                "the last column kept); right-padded prompts would "
                "generate from a pad position."
            )
    if temperature < 0.0:
        raise SMPValidationError(
            "temperature must be >= 0 (0 = greedy); a negative value "
            "would sample from the probability-inverted distribution."
        )
    if temperature > 0.0 and rng is None:
        raise SMPValidationError("temperature > 0 requires rng=jax.random.key(...)")
    if temperature == 0.0 and num_beams == 1 and (
        top_k is not None or top_p is not None
    ):
        # HF warns here; we refuse — a user passing top_p=0.9 without a
        # temperature would silently get greedy output.
        raise SMPValidationError(
            "top_k/top_p have no effect with temperature == 0 (greedy "
            "argmax); pass temperature > 0 to sample (e.g. temperature"
            "=1.0), or drop the filters."
        )
    if top_k is not None and top_k < 1:
        raise SMPValidationError("top_k must be >= 1.")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise SMPValidationError("top_p must be in (0, 1].")
    if num_beams > 1 and (temperature > 0.0 or top_k is not None
                          or top_p is not None):
        raise SMPValidationError(
            "beam search is greedy (num_beams > 1 requires temperature == "
            "0 and no top_k/top_p filters)."
        )
    if not 1 <= num_return_sequences <= num_beams:
        raise SMPValidationError(
            "num_return_sequences must be in [1, num_beams]."
        )
    if rng is None:
        rng = jax.random.key(0)

    # Decode-length shape buckets (SMP_SHAPE_BUCKETS "seq" sizes, the
    # PR-11 policy): ragged (prompt-len, max-new-tokens) pairs round UP
    # to bucket boundaries so serving-style traffic reuses cached
    # programs instead of churning the _COMPILED LRU. max_new_tokens
    # buckets for every decoder-only model (the extra steps are sliced
    # off; EOS-frozen rows just emit pad there); prompt length buckets by
    # LEFT-padding through the existing padded-prompt machinery, so it
    # needs a mask-capable module (the smp.nn family). Greedy output is
    # invariant; stochastic sampling draws from the bucketed key schedule
    # (split(rng, bucketed_max_new) — reproducible for a fixed bucket
    # config, documented in README). Beam search is excluded: its
    # hypothesis scores normalize by max_new_tokens, so padding it would
    # change the ranking.
    orig_input_ids = input_ids
    orig_T = input_ids.shape[1]
    orig_new = max_new_tokens
    if num_beams == 1 and not seq2seq:
        from smdistributed_modelparallel_tpu.utils import exec_cache

        policy = exec_cache.bucket_policy()
        seqs = (policy or {}).get("seq")
        if seqs:
            padded = False
            unbucketable = False
            limit = getattr(module, "max_len", None) or getattr(
                module, "num_positions", None
            )
            new_b = exec_cache.bucket_for(max_new_tokens, seqs)
            if new_b is not None and limit is not None and (
                orig_T + new_b > limit
            ):
                # Never let a bucket push a fitting request past the
                # model's position limit — decode length stays exact.
                new_b = None
            if new_b is None:
                unbucketable = True
            elif new_b != max_new_tokens:
                max_new_tokens = new_b
                padded = True
            t_b = exec_cache.bucket_for(orig_T, seqs)
            if t_b is not None and limit is not None and (
                t_b + max_new_tokens > limit
            ):
                t_b = None
            if t_b is not None and t_b != orig_T:
                import inspect

                if "attention_mask" in inspect.signature(
                    type(module).__call__
                ).parameters:
                    nb = input_ids.shape[0]
                    pad_w = t_b - orig_T
                    input_ids = jnp.concatenate(
                        [jnp.full((nb, pad_w), pad_token_id,
                                  input_ids.dtype), input_ids], axis=1
                    )
                    keep = (
                        attention_mask.astype(jnp.int32)
                        if attention_mask is not None
                        else jnp.ones((nb, orig_T), jnp.int32)
                    )
                    attention_mask = jnp.concatenate(
                        [jnp.zeros((nb, pad_w), jnp.int32), keep], axis=1
                    )
                    padded = True
                else:
                    unbucketable = True
            elif t_b is None:
                unbucketable = True
            # "padded" wins over "unbucketable": a call whose decode
            # length bucketed (program shared) but whose prompt dim
            # couldn't must count as a bucket hit, not a miss.
            exec_cache.record_bucket(
                "padded" if padded
                else ("unbucketable" if unbucketable else "exact")
            )

    B, T = input_ids.shape
    cache_len = (1 + max_new_tokens) if seq2seq else (T + max_new_tokens)
    limit = getattr(module, "max_len", None) or getattr(
        module, "num_positions", None
    )
    if limit is not None and cache_len > limit:
        raise SMPValidationError(
            f"{'decoder length' if seq2seq else 'prompt'} + max_new_tokens "
            f"({cache_len}) exceeds the model's position limit ({limit})."
        )
    if limit is not None and seq2seq and T > limit:
        raise SMPValidationError(
            f"encoder prompt length ({T}) exceeds the model's position "
            f"limit ({limit})."
        )

    has_mask = encoder_mask is not None
    half = state.cfg.half_dtype if state.cfg is not None else None
    key = None
    try:
        # The mesh is part of the key: sharding constraints traced into the
        # program bind the mesh active at trace time (smp.reset + re-init
        # with a different mesh must not reuse a stale program).
        from smdistributed_modelparallel_tpu import quant as _quant

        key = (module, B, T, max_new_tokens, float(temperature), top_k,
               top_p, eos_token_id, pad_token_id, decoder_start_token_id,
               has_mask, attention_mask is not None, num_beams,
               float(length_penalty), num_return_sequences, str(half),
               state.mesh if state.initialized else None
               ) + _quant.serving_key_suffix()
        compiled = _COMPILED.get(key)
        if compiled is not None:
            _COMPILED.move_to_end(key)
    except TypeError:  # unhashable module fields: compile uncached
        key = None
        compiled = None
    if compiled is None:
        decode_mod = _decode_clone(module, cache_len)
        if num_beams > 1:
            run = _build_beam_generator(
                decode_mod, max_new_tokens, num_beams, eos_token_id,
                pad_token_id, float(length_penalty), seq2seq,
                decoder_start_token_id, num_return_sequences, half,
            )
        elif seq2seq:
            sampler = _make_sampler(float(temperature), top_k, top_p)
            run = _build_seq2seq_generator(
                decode_mod, max_new_tokens, sampler, eos_token_id,
                pad_token_id, decoder_start_token_id, half,
            )
        else:
            sampler = _make_sampler(float(temperature), top_k, top_p)
            run = _build_generator(decode_mod, max_new_tokens, sampler,
                                   eos_token_id, pad_token_id, half)
        compiled = jax.jit(run)
        if key is not None:
            _COMPILED[key] = compiled
            while len(_COMPILED) > _COMPILED_CAP:
                _COMPILED.popitem(last=False)

    args = (
        (params, input_ids, encoder_mask, rng) if seq2seq
        else (params, input_ids, attention_mask, rng)
    )
    mesh = state.mesh if state.initialized else None
    if mesh is not None:
        with jax.set_mesh(mesh):
            out = compiled(*args)
    else:
        out = compiled(*args)
    if T != orig_T or max_new_tokens != orig_new:
        # Bucketed run: drop the left-pad columns and the extra decode
        # steps — callers see exactly the (prompt, max_new) they asked
        # for.
        out = jnp.concatenate(
            [orig_input_ids, out[:, T:T + orig_new]], axis=1
        )
    return out
