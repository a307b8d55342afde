"""Low-precision matmul + KV-cache quantization — ``smp.quant``.

TPU extension (no reference counterpart): the reference
(``smdistributed.modelparallel``) stops at an fp16 dynamic loss scaler
(``fp16/loss_scaler.py``); it has no low-precision matmul or KV path at
all. This module is one knob family with two halves:

**Training** — ``matmul_precision: fp8`` (env ``SMP_MATMUL_PRECISION``)
dispatches the framework's matmul seams (the tp ring's chunk matmuls,
the fused QKV Pallas kernel, the DistributedLinear/Transformer einsum
paths, the bias+GELU epilogue input, the attention score inputs)
through fp8: e4m3 forward operands, e5m2 gradients, with DELAYED
scaling — each quantization site carries an amax history whose running
max sets the next step's dequantization scale, exactly the recipe of
the Transformer-Engine/TE fp8 ladder. The per-site state
(``QuantState``) threads through the step like the fp16 loss scaler:
it enters the compiled program as an input pytree, per-microbatch amax
observations ride out of the microbatch scan as stacked outputs, and
the program returns the rolled history + refreshed scales, which the
runner absorbs back into ``state.quant_state`` (checkpointed beside
the loss scaler as ``quant_states.pt``; see ``checkpoint.py``).

**Serving** — ``SMP_KV_QUANT=int8`` stores the paged KV pool
(``nn/utils.PagedKVCache``) as int8 with per-block-per-head scales
(pool bytes ~ halved -> ~2x servable concurrency per chip),
dequantizing at the decode-attention gather; ``SMP_DECODE_WEIGHTS=int8``
adds weight-only int8 (per-output-channel scales, quantized ONCE at
``ServingEngine.adopt_params``/load) for the memory-bound decode
matmuls, with ``smp.generate`` running the numerics-identical
fake-quant path so the two decode stacks stay token-parity-checkable
against each other.

Canonicalization contract (the PR-12/15 discipline): every knob here
resolves through a canonical mode function (``matmul_precision_mode``,
``kv_quant_mode``, ``decode_weights_mode``); defaults contribute
NOTHING to step keys, exec-cache knob facts, serving program keys, or
X-ray fingerprints — default-knob programs stay byte-identical to
pre-knob builds. fp8 does not compose with pipeline parallelism or the
ZeRO-3 manual-gradient path yet; the mode canonicalizes to "bf16"
there with a one-time warning, so the key/fact story stays coherent.

CPU/interpret note: XLA:CPU upcasts f8 dot operands to f32 inside the
compiled program (the dots remain *fp8-origin*: their operands are
converts from f8 — the X-ray ``quant`` census counts both forms), so
CPU smoke runs prove plumbing + numerics parity only; the fp8 speed
claim has not been measured on the chip.
"""

import functools
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np

from smdistributed_modelparallel_tpu.backend.state import state
from smdistributed_modelparallel_tpu.utils.logger import get_logger

logger = get_logger()

# ----------------------------------------------------------------------
# Knob resolution (canonical modes)
# ----------------------------------------------------------------------

_WARNED = set()


def _warn_once(key, msg, *args):
    if key in _WARNED:
        return
    _WARNED.add(key)
    logger.warning(msg, *args)


def matmul_precision_mode(cfg=None):
    """The effective training matmul precision: the config knob
    (``matmul_precision``, env ``SMP_MATMUL_PRECISION``), canonicalized
    to "bf16" whenever it cannot engage: pipeline parallelism (the
    pipelined executors own their own grad plumbing — the amax scan
    outputs have no seat there yet) and ZeRO-3 (the manual-grad vmap
    would trap the amax observations inside its trace). Keyed into the
    step cache / exec-cache knob facts in this canonical form so an
    idle knob never moves a key."""
    cfg = cfg if cfg is not None else state.cfg
    if cfg is None:
        return "bf16"
    mode = getattr(cfg, "matmul_precision", "bf16") or "bf16"
    if mode == "bf16":
        return "bf16"
    if getattr(cfg, "pipeline_parallel_degree", 1) > 1:
        _warn_once(
            ("pp", mode),
            "matmul_precision=%s requested with pipeline_parallel_degree "
            "> 1; fp8 does not compose with the pipelined executors yet "
            "— keeping bf16 matmuls.", mode,
        )
        return "bf16"
    if getattr(cfg, "sharded_params", "none") == "zero3":
        _warn_once(
            ("zero3", mode),
            "matmul_precision=%s requested with sharded_params=zero3; "
            "fp8 does not compose with the ZeRO-3 manual-gradient path "
            "yet — keeping bf16 matmuls.", mode,
        )
        return "bf16"
    return mode


def kv_quant_mode():
    """Serving paged-KV pool precision: ``SMP_KV_QUANT`` (default
    "none"; "int8" stores the pool int8 with per-block-per-head
    scales)."""
    v = os.environ.get("SMP_KV_QUANT", "none").strip().lower() or "none"
    if v in ("", "0", "none", "off", "bf16"):
        return "none"
    if v != "int8":
        raise ValueError(
            f"SMP_KV_QUANT={v!r}: expected 'int8' or unset/none."
        )
    return "int8"


def decode_weights_mode():
    """Serving/decode weight precision: ``SMP_DECODE_WEIGHTS`` (default
    "none"; "int8" = weight-only int8 with per-output-channel scales,
    quantized once at ``adopt_params``/load)."""
    v = os.environ.get("SMP_DECODE_WEIGHTS", "none").strip().lower() or "none"
    if v in ("", "0", "none", "off", "bf16"):
        return "none"
    if v != "int8":
        raise ValueError(
            f"SMP_DECODE_WEIGHTS={v!r}: expected 'int8' or unset/none."
        )
    return "int8"


def serving_key_suffix():
    """Serving-program cache-key components for the quant knobs.
    Defaults contribute NOTHING (byte-identical key tuples to pre-knob
    builds); a knob flip appends facts, so the flipped program is a
    verified miss, never a warm hit of the other pool layout."""
    suffix = ()
    if kv_quant_mode() != "none":
        suffix += (("kv_quant", kv_quant_mode()),)
    if decode_weights_mode() != "none":
        suffix += (("decode_weights", decode_weights_mode()),)
    return suffix


# ----------------------------------------------------------------------
# fp8 formats + the static site registry
# ----------------------------------------------------------------------

E4M3_MAX = 448.0
E5M2_MAX = 57344.0
AMAX_HISTORY = 16

# Static quantization slots: "<site>.<role>" with role x (fwd input)
# and w (fwd weight) — the delayed-scaling (stateful) seams. Backward
# cotangents carry NO slot: ``jax.custom_vjp`` traces its bwd rule into
# a jaxpr of its own, so a bwd-side amax observation could never escape
# into the step's state — the e5m2 cotangent instead uses just-in-time
# CURRENT scaling (``amax(g) / E5M2_MAX`` computed where g exists),
# which is stateless and at least as tight as a delayed estimate. The
# registry is a FIXED tuple so the QuantState pytree structure is known
# before the first trace (it is a program input); instances of one seam
# family share a slot — the ``nn.scan`` layer stack shares one trace
# anyway, and the shared running max is a conservative
# (never-overflowing) scale for every member.
SITE_SLOTS = (
    "qkv.x", "qkv.w",
    "attn_proj.x", "attn_proj.w",
    "mlp_fc.x", "mlp_fc.w",
    "mlp_proj.x", "mlp_proj.w",
    "linear_col.x", "linear_col.w",
    "linear_row.x", "linear_row.w",
    "ring_ag.x", "ring_ag.w",
    "ring_rs.x", "ring_rs.w",
    "gelu_in.x",
    "attn_q.x", "attn_k.x",
)
_SLOT_INDEX = {s: i for i, s in enumerate(SITE_SLOTS)}


def _slot_fmax(slot):
    return E5M2_MAX if slot.endswith(".g") else E4M3_MAX


def _slot_dtype(slot):
    import jax.numpy as jnp

    return jnp.float8_e5m2 if slot.endswith(".g") else jnp.float8_e4m3fn


# ----------------------------------------------------------------------
# QuantState — the host-side delayed-scaling state (the loss-scaler
# pattern: lives on smp.state, updated from each step's outputs,
# checkpointed as a plain state dict).
# ----------------------------------------------------------------------


class QuantState:
    """Per-slot amax history + dequantization scales.

    ``scale[i]`` is the DIVISOR applied before the f8 cast (and the
    multiplier at dequant): ``x8 = cast(clip(x / scale))``. Delayed
    scaling: scale derives from the running max of the previous
    ``AMAX_HISTORY`` steps' amax, ``max_amax / fmax`` — the current
    step quantizes with last step's statistics, so the whole update is
    one program with no mid-step host sync. Scales start at 1.0 (the
    TE convention) until a history entry lands."""

    def __init__(self):
        n = len(SITE_SLOTS)
        self.amax_history = np.zeros((n, AMAX_HISTORY), np.float32)
        self.scale = np.ones((n,), np.float32)

    def arrays(self):
        import jax.numpy as jnp

        return {
            "amax_history": jnp.asarray(self.amax_history),
            "scale": jnp.asarray(self.scale),
        }

    def absorb(self, out):
        """Install a step program's rolled state and publish the
        telemetry gauges (``smp_quant_amax`` / ``smp_quant_scale``,
        latest per site)."""
        from smdistributed_modelparallel_tpu.utils.telemetry import (
            record_quant_state,
        )

        self.amax_history = np.asarray(out["amax_history"], np.float32)
        self.scale = np.asarray(out["scale"], np.float32)
        record_quant_state(
            SITE_SLOTS, self.amax_history[:, 0], self.scale
        )

    def state_dict(self):
        return {
            "amax_history": np.asarray(self.amax_history, np.float32),
            "scale": np.asarray(self.scale, np.float32),
            "slots": list(SITE_SLOTS),
        }

    def load_state_dict(self, sd):
        """Slot-name keyed restore: resuming under a build with a
        different slot registry keeps the intersection (new slots keep
        their fresh-start 1.0 scale)."""
        slots = list(sd.get("slots", ()))
        hist = np.asarray(sd["amax_history"], np.float32)
        scale = np.asarray(sd["scale"], np.float32)
        for j, name in enumerate(slots):
            i = _SLOT_INDEX.get(name)
            if i is None:
                continue
            h = min(hist.shape[1], AMAX_HISTORY)
            self.amax_history[i, :h] = hist[j, :h]
            self.scale[i] = scale[j]


def ensure_state():
    """``state.quant_state``, created on first use (fp8 mode only)."""
    qs = getattr(state, "quant_state", None)
    if qs is None:
        qs = QuantState()
        state.quant_state = qs
    return qs


# ----------------------------------------------------------------------
# Trace-time context: installed by the step runner around the traced
# program (the health-collector pattern). Seams read their slot's
# scale from the context and record amax observations; the microbatch
# scan body drains the observations into stacked scan outputs, and the
# runner folds them into the rolled state the program returns.
# ----------------------------------------------------------------------

_TRACE = threading.local()


class _QuantTrace:
    def __init__(self, arrays):
        self.arrays = arrays
        self.pending = {}       # slot -> amax tracer (current trace level)
        self.last_drain = ()    # slot order of the most recent scan_drain

    def scale_for(self, slot):
        return self.arrays["scale"][_SLOT_INDEX[slot]]

    def record(self, slot, amax):
        import jax.numpy as jnp

        tgt = self.pending
        if slot in tgt:
            try:
                tgt[slot] = jnp.maximum(tgt[slot], amax)
            except Exception:
                # The stored value is a dead tracer from an abandoned or
                # completed sub-trace (lax.scan traces bodies more than
                # once; a differentiated nn.scan re-traces its body for
                # the backward pass). The live re-trace re-records, so
                # replacing is exact.
                tgt[slot] = amax
        else:
            tgt[slot] = amax


class step_trace:
    """Context manager installing the quant trace for one program
    trace. ``arrays=None`` (bf16 mode) installs nothing — the traced
    program is byte-identical to a build without this module."""

    def __init__(self, arrays):
        self.arrays = arrays
        self.ctx = None

    def __enter__(self):
        if self.arrays is not None:
            self.ctx = _QuantTrace(self.arrays)
            _TRACE.ctx = self.ctx
        return self.ctx

    def __exit__(self, *exc):
        _TRACE.ctx = None
        return False


def _ctx():
    return getattr(_TRACE, "ctx", None)


def fp8_trace_active():
    """Whether the CURRENT trace should dispatch fp8 matmuls: a quant
    trace context is installed (only the step runner installs one, and
    only under ``matmul_precision: fp8``). Serving / generate / eager
    forwards see False and keep the bf16 paths."""
    return _ctx() is not None


def _drain_live(ctx):
    """Pop the pending entries whose tracers are still usable at the
    current trace level, sorted by slot name. Entries recorded inside a
    completed sub-trace (e.g. the backward rules the layer scan's
    transpose re-traces in its own body) are dead here and silently
    dropped — their slots simply see no observation this step, which
    delayed scaling tolerates by design (the scale is a running max
    over AMAX_HISTORY steps)."""
    import jax.numpy as jnp

    live = []
    for slot in sorted(ctx.pending):
        val = ctx.pending[slot]
        try:
            # Any op on a leaked tracer raises UnexpectedTracerError;
            # on a live one it's a no-op the compiler folds away.
            val = jnp.maximum(val, val)
        except Exception:
            continue
        live.append((slot, val))
    ctx.pending.clear()
    return live


def scan_drain():
    """Drain the amax observations recorded during the current scan
    body's trace, as a tuple ordered by sorted slot name — the scan
    body returns it as extra stacked outputs (ys). () when inactive or
    nothing recorded. Each drain fixes its own slot order
    (``last_drain``): the layer scan inside the microbatch scan drains
    a different slot set than the microbatch body itself."""
    ctx = _ctx()
    if ctx is None or not ctx.pending:
        if ctx is not None:
            ctx.last_drain = ()
        return ()
    live = _drain_live(ctx)
    ctx.last_drain = tuple(s for s, _ in live)
    return tuple(v for _, v in live)


def scan_was_drained():
    """Whether the most recent ``scan_drain`` (the just-completed
    scan's body trace) shipped any observations — the unpack flag for
    that scan's wrapped ys. Consume with ``absorb_stacked`` before any
    further drain runs."""
    ctx = _ctx()
    return ctx is not None and bool(ctx.last_drain)


def absorb_stacked(stacked):
    """Fold a completed scan's stacked amax outputs ([length] leading
    axis each, ordered like the body's ``scan_drain``) back into the
    CURRENT trace level's pending observations (max over the scanned
    axis). Inside a nested scan this re-arms the enclosing body's own
    drain; at the top level the records wait for ``finalize``. Clears
    the drain marker — each drain is consumed exactly once."""
    import jax.numpy as jnp

    ctx = _ctx()
    if ctx is None or not stacked:
        return
    slots, ctx.last_drain = ctx.last_drain, ()
    for slot, arr in zip(slots, stacked):
        ctx.record(slot, jnp.max(arr))


def finalize(arrays):
    """The program-output state: roll each observed slot's history by
    one (newest at column 0) and refresh every scale from its
    history's running max — ``max_amax / fmax`` once any history entry
    landed, 1.0 before (the fresh-start convention). Unobserved slots
    roll nothing (an eval-only program leaves the grad slots' history
    untouched). Consumes whatever reached the top-level pending set —
    scan-absorbed maxima plus any seam traced outside the scans."""
    import jax.numpy as jnp

    ctx = _ctx()
    hist = arrays["amax_history"]
    observed = dict(_drain_live(ctx)) if ctx is not None else {}
    if observed:
        rows = []
        for i, slot in enumerate(SITE_SLOTS):
            if slot in observed:
                rows.append(
                    jnp.concatenate(
                        [observed[slot][None].astype(jnp.float32),
                         hist[i, :-1]]
                    )
                )
            else:
                rows.append(hist[i])
        hist = jnp.stack(rows)
    fmax = jnp.asarray(
        [_slot_fmax(s) for s in SITE_SLOTS], jnp.float32
    )
    running = jnp.max(hist, axis=1)
    scale = jnp.where(running > 0.0, running / fmax, 1.0)
    return {"amax_history": hist, "scale": scale}


# ----------------------------------------------------------------------
# The fp8 ops (delayed-scaling quantize + f8-operand dots)
# ----------------------------------------------------------------------


def _record_amax(x, slot):
    """Record this step's amax observation for ``slot`` — MUST run in
    the caller's trace, never inside a ``custom_vjp`` fwd/bwd rule
    (those trace into jaxprs of their own, and a tracer recorded there
    is dead the moment the rule's trace closes)."""
    import jax.numpy as jnp

    _ctx().record(slot, jnp.max(jnp.abs(x)).astype(jnp.float32))


def _cast_f8(x, slot):
    """(x8, scale): clip/scale ``x`` into the slot's f8 format with the
    delayed scale. Pure — safe inside custom_vjp rules; the caller-side
    wrapper records the amax separately."""
    import jax.numpy as jnp

    d = _ctx().scale_for(slot)
    fmax = _slot_fmax(slot)
    x8 = jnp.clip(
        x.astype(jnp.float32) / d, -fmax, fmax
    ).astype(_slot_dtype(slot))
    return x8, d


def _cast_e5m2_current(g):
    """(g8, scale): e5m2 cotangent with just-in-time CURRENT scaling —
    ``amax(g) / E5M2_MAX`` computed from the tensor itself (stateless;
    see the SITE_SLOTS note on why bwd cannot feed delayed state)."""
    import jax.numpy as jnp

    ag = jnp.max(jnp.abs(g)).astype(jnp.float32)
    d = jnp.where(ag > 0.0, ag / E5M2_MAX, 1.0)
    g8 = jnp.clip(
        g.astype(jnp.float32) / d, -E5M2_MAX, E5M2_MAX
    ).astype(jnp.float8_e5m2)
    return g8, d


def _f8_dot(a8, b8, scale):
    """f32 <- f8 x f8 dot (contract a's last dim with b's first),
    dequantized by ``scale``. The dot's operands are genuine f8 arrays:
    TPU MXUs with native f8 consume them directly; XLA:CPU upcasts
    them (the X-ray census counts those as fp8-ORIGIN dots)."""
    import jax
    import jax.numpy as jnp

    y = jax.lax.dot_general(
        a8, b8, (((a8.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return y * scale


def _pallas_f8_mm(x8, w8, interpret):
    """The fp8 rung of the Pallas matmul ladder: the fused-QKV kernel's
    tiling with f8 operand refs (``ops/pallas_qkv.matmul_bias_fp8``);
    dequant + bias stay in the XLA epilogue."""
    from smdistributed_modelparallel_tpu.ops.pallas_qkv import (
        matmul_bias_fp8,
    )

    return matmul_bias_fp8(x8, w8, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fp8_mm2d(x2, w2, b, site, use_pallas, interpret):
    y, _ = _fp8_mm2d_fwd(x2, w2, b, site, use_pallas, interpret)
    return y


def _fp8_mm2d_fwd(x2, w2, b, site, use_pallas, interpret):
    x8, dx = _cast_f8(x2, site + ".x")
    w8, dw = _cast_f8(w2, site + ".w")
    if use_pallas:
        y = _pallas_f8_mm(x8, w8, interpret) * (dx * dw)
    else:
        y = _f8_dot(x8, w8, dx * dw)
    if b is not None:
        y = y + b.astype(y.dtype)
    y = y.astype(x2.dtype)
    # Zero-size dtype carriers: custom_vjp residuals must be JAX types,
    # and the saved operands are f8 — the originals' dtypes ride along
    # as empty arrays so the cotangents cast back correctly.
    res = (x8, dx, w8, dw,
           jnp.zeros((0,), x2.dtype), jnp.zeros((0,), w2.dtype),
           None if b is None else jnp.zeros((0,), b.dtype))
    return y, res


def _fp8_mm2d_bwd(site, use_pallas, interpret, res, g):
    x8, dx, w8, dw, x_dt, w_dt, b_dt = res
    g8, dg = _cast_e5m2_current(g)
    # e5m2 cotangent against the SAVED f8 operands (the fp8 residency
    # win: no bf16 copies of x/w survive the forward).
    dx2 = _f8_dot(g8, w8.T, dg * dw).astype(x_dt.dtype)
    dw2 = _f8_dot(x8.T, g8, dx * dg).astype(w_dt.dtype)
    db = None if b_dt is None else jnp.sum(g, axis=0).astype(b_dt.dtype)
    return dx2, dw2, db


_fp8_mm2d.defvjp(_fp8_mm2d_fwd, _fp8_mm2d_bwd)


def fp8_matmul(x, w, site, *, bias=None, n_contract=1, use_pallas=False,
               interpret=False):
    """``x @ w (+ bias)`` through the fp8 delayed-scaling path,
    contracting x's last ``n_contract`` dims with w's first
    ``n_contract`` dims (the einsum shapes of the transformer seams).
    Forward operands e4m3, backward cotangent e5m2; scales come from
    the step's ``QuantState`` and this call records the amax that
    feeds the next step's scales."""
    import numpy as _np

    lead = x.shape[:x.ndim - n_contract]
    k = int(_np.prod(x.shape[x.ndim - n_contract:], dtype=_np.int64))
    out_shape = w.shape[n_contract:]
    n = int(_np.prod(out_shape, dtype=_np.int64)) if out_shape else 1
    x2 = x.reshape(-1, k)
    w2 = w.reshape(k, n)
    b1 = None if bias is None else bias.reshape(n)
    # Amax observations happen HERE, in the caller's trace — the
    # custom_vjp rules below trace into their own jaxprs and anything
    # recorded there could never reach the step's quant outputs.
    _record_amax(x2, site + ".x")
    _record_amax(w2, site + ".w")
    y = _fp8_mm2d(x2, w2, b1, site, use_pallas, interpret)
    return y.reshape(lead + out_shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _fake_quant(x, slot):
    y, _ = _fake_quant_fwd(x, slot)
    return y


def _fake_quant_fwd(x, slot):
    x8, d = _cast_f8(x, slot)
    return (x8.astype(jnp.float32) * d).astype(x.dtype), None


def _fake_quant_bwd(slot, _, g):
    return (g,)


_fake_quant.defvjp(_fake_quant_fwd, _fake_quant_bwd)


def fake_quant(x, slot):
    """fp8 round-trip (quantize -> dequantize) with the slot's delayed
    scale and a straight-through gradient — the handoff precision for
    non-dot consumers (the bias+GELU epilogue input, the attention
    score operands inside the flash kernel's bf16 compute, the ring's
    chunk-matmul operands at the shard_map boundary). Records the amax
    in THIS trace, then round-trips through the pure custom_vjp."""
    _record_amax(x, slot)
    return _fake_quant(x, slot)


# ----------------------------------------------------------------------
# Serving: weight-only int8 (per-output-channel scales)
# ----------------------------------------------------------------------


def _weight_leaf(leaf):
    """Weight-only int8 eligibility: float leaves with a contraction
    structure (ndim >= 2) — Dense/attention kernels and embeddings;
    biases, layernorm vectors and scalars stay put."""
    dt = getattr(leaf, "dtype", None)
    return (
        dt is not None
        and jnp.issubdtype(dt, jnp.floating)
        and getattr(leaf, "ndim", 0) >= 2
    )


def quantize_decode_params(params):
    """One-shot weight-only int8: eligible leaves become int8 with a
    per-OUTPUT-channel (last-axis) f32 scale; the rest ride unchanged.
    Returns ``{"q": tree, "s": tree}`` — a plain pytree, so the
    serving programs take it as a call argument and ``adopt_params``
    stays a zero-recompile pointer swap. Selection is structural
    (dtype + ndim), so ``dequantize_decode_params`` inverts it without
    side metadata."""
    def q_leaf(leaf):
        if not _weight_leaf(leaf):
            return leaf
        amax = jnp.max(
            jnp.abs(leaf.astype(jnp.float32)),
            axis=tuple(range(leaf.ndim - 1)),
        )
        scale = jnp.maximum(amax / 127.0, 1e-12).astype(jnp.float32)
        q = jnp.round(leaf.astype(jnp.float32) / scale)
        return jnp.clip(q, -127, 127).astype(jnp.int8)

    def s_leaf(leaf):
        if not _weight_leaf(leaf):
            return jnp.zeros((), jnp.float32)
        amax = jnp.max(
            jnp.abs(leaf.astype(jnp.float32)),
            axis=tuple(range(leaf.ndim - 1)),
        )
        return jnp.maximum(amax / 127.0, 1e-12).astype(jnp.float32)

    return {
        "q": jax.tree_util.tree_map(q_leaf, params),
        "s": jax.tree_util.tree_map(s_leaf, params),
    }


def dequantize_decode_params(qparams, dtype=None):
    """Invert ``quantize_decode_params`` inside the serving program:
    int8 leaves dequantize per channel to ``dtype`` (default f32);
    pass-through leaves return untouched. The int8 copies are what
    lives in HBM — the dequant materializes at use, which is the
    weight-only decode contract (memory-bound matmuls read half the
    bytes)."""
    tgt = dtype or jnp.float32

    def d_leaf(q, s):
        if getattr(q, "dtype", None) == jnp.int8:
            return (q.astype(jnp.float32) * s).astype(tgt)
        return q

    return jax.tree_util.tree_map(d_leaf, qparams["q"], qparams["s"])


def fake_quant_decode_params(params):
    """The ``smp.generate`` twin of the serving int8 path: the same
    per-channel int8 round-trip applied in-program (values identical
    to store-int8 + dequant), so generate/serving outputs stay
    comparable token-for-token under the same knob."""
    q = quantize_decode_params(params)
    return jax.tree_util.tree_map(
        lambda p, qq, ss: (
            (qq.astype(jnp.float32) * ss).astype(p.dtype)
            if getattr(qq, "dtype", None) == jnp.int8 else p
        ),
        params, q["q"], q["s"],
    )


# ----------------------------------------------------------------------
# Serving: int8 paged-KV helpers (per-block-per-head scales)
# ----------------------------------------------------------------------


def kv_pool_dtype(requested):
    import jax.numpy as _jnp

    return _jnp.int8 if kv_quant_mode() == "int8" else requested


def kv_quantize_append(pool_i8, scale, k, blk_flat):
    """One paged append under int8: fold the incoming tokens' per-head
    amax into the touched blocks' scales (scales only GROW), requantize
    the pool under the grown scales (``q_new = round(q_old *
    old/new)`` — exact where the scale didn't move), and quantize the
    new tokens with the post-growth scales.

    Args:
      pool_i8: [nb, bt, H, hd] int8 pool (flattened writes happen by
        the caller).
      scale: [nb, H] f32 per-block-per-head scales.
      k: [N, H, hd] incoming tokens (flattened rows).
      blk_flat: [N] int32 destination block per token.

    Returns (requantized pool_i8, new scale, q_tokens int8 [N, H, hd]).
    """
    tok_amax = jnp.max(jnp.abs(k.astype(jnp.float32)), axis=2)  # [N, H]
    grown = scale.at[blk_flat].max(tok_amax / 127.0)
    new_scale = jnp.maximum(grown, 1e-12)
    ratio = scale / new_scale                                   # <= 1
    requant = jnp.round(
        pool_i8.astype(jnp.float32) * ratio[:, None, :, None]
    ).astype(jnp.int8)
    d = jnp.take(new_scale, blk_flat, axis=0)                   # [N, H]
    q_tok = jnp.clip(
        jnp.round(k.astype(jnp.float32) / d[:, :, None]), -127, 127
    ).astype(jnp.int8)
    return requant, new_scale, q_tok


def kv_dequantize_gather(vals_i8, scale, slot_blocks, dtype):
    """Dequantize gathered KV columns: ``vals_i8`` [B, S, H, hd] int8
    gathered by flat slot, ``slot_blocks`` [B, S] the pool block each
    gathered column came from."""
    d = jnp.take(scale, slot_blocks, axis=0)                    # [B,S,H]
    return (vals_i8.astype(jnp.float32) * d[..., None]).astype(dtype)
