"""Mixture-of-Experts layers: a one-hot capacity design over the ``ep`` mesh
axis, and a dropless sort-and-group design for a chip's share of the experts.

NEW CAPABILITY relative to the reference: SURVEY §2.6 records MoE/EP as
absent from ``smdistributed.modelparallel`` v1.12.1.

``DistributedMoE`` (GShard/Switch dense dispatch):

- routing, position-in-expert bookkeeping, and capacity dropping are pure
  einsum/cumsum math on one-hot tensors (no scatters, no dynamic shapes —
  everything tiles onto the MXU and fuses);
- expert FFNs are ONE batched matmul over ``[E, C, D]`` with the expert
  axis sharded over ``ep`` (and the FFN hidden dim over ``tp``);
- the token->expert shuffle is not hand-written: tokens are batch-sharded
  over the data axes (which include ``ep``) while expert tensors are
  ep-sharded, so GSPMD lowers the dispatch/combine einsums to the
  all-to-all exchanges over ICI;
- its ``[N, E, C]`` masks grow with tokens x experts x capacity (8,192
  tokens, 256 experts, capacity 400: 8.4e8 elements each) and a token over
  an expert's capacity is dropped.

``DistributedDroplessMoE`` (many small experts, a share of them here):

- the layer is told which experts it holds (``held = (first, count)`` of
  the published ``num_experts``; all of them by default). The router keeps
  its ``num_experts`` outputs and its ``top_k``; the layer computes what
  its own experts add for the tokens routed to them, plus the shared
  expert. What absent experts would add is left out: this is the part of
  the result one expert-parallel rank computes, and on one chip it runs
  without the exchange that would gather the other ranks' parts;
- assignments are sorted by expert (one ``argsort``), and the held
  experts' rows go through a grouped matrix product
  (``jax.lax.ragged_dot``, which the TPU compiler lowers to its own
  grouped-matmul kernel) in chunks of sorted rows (``_chunk_rows``:
  ``ROWS_PER_CHUNK``, or a third of an even router's load where that is
  more). The loop runs over the chunks that hold rows, so the work follows
  the rows that landed here and no assignment is ever dropped: the row
  buffer's bound is the worst case, every token on every held expert;
- the backward pass is written out (``_held_bwd``: the loop's trip count
  is data) and so is a chunk's chain (``_chunk_grads``), which
  ``jax.vjp`` over the forward's two products used to build: three
  grouped products over rows a chunk, all in forward form (the first
  product run again, ``u = g @ w_down^T`` with an fp32 result, ``dr = d_h
  @ w_gate_up^T``) on weights transposed once a layer call, before the
  loop. Differentiating the forward's closures ran four (a vjp evaluates
  its primal) and read each weight tensor two ways in one loop body, so
  the compiler re-laid both inside it, once a chunk. The second product
  ``y = h_act @ w_down`` is not formed: its cotangent is ``w * g``
  whatever ``y`` holds, and its one other reader, the combine weights'
  gradient ``sum_d y * g``, is ``sum_f h_act * u``, the same double sum
  taken in the other order. ``g`` enters the chain only as an operand of
  the rows' dtype, so it is cast once a layer call and gathered in that
  dtype;
- the experts' weight gradients are summed where they are made: the
  backward pass carries one fp32 sum a layer call for each
  of the two weight tensors, and a chunk's
  ``ops/pallas_grouped_wgrad.grouped_wgrad`` adds to the blocks of the
  experts it holds rows of and touches no other. The kernel stands aside
  (``_wgrad_kernel_engages``: not on a TPU, a chunk that is not whole row
  tiles, a width that is not a multiple of 128, a mesh of more than one
  device) for the grouped products' own transposes, which make a
  [held, D, 2F] and a [held, F, D] product a chunk in the operands' dtype
  that is converted and added afterwards.
  ``smp_moe_wgrad_kernel_engaged{layer}`` says which was traced in;
- a chunk's rows are summed back to their tokens where the sum is: the
  forward pass carries one fp32 [tokens, D] sum a layer call (``out``) and
  the backward pass one (``dx``), and a chunk's
  ``ops/pallas_row_scatter_add.row_scatter_add`` streams the sum through
  VMEM once, adding each row that belongs to its token's row, with the
  combine weights applied inside (forward). It stands aside
  (``_combine_kernel_engages``: not on a TPU, a mesh of more than one
  device, a width that is not a multiple of 128, chunks too sparse over
  the tokens for a stream of the whole sum to pay) for XLA's scatter-add
  of the same fp32 terms, one row at a time.
  ``smp_moe_combine_kernel_engaged{layer}`` says which was traced in;
- gated experts without biases (``act(x W_gate) * (x W_up)) W_down``),
  top-k weights renormalised over all ``top_k`` (held or not) and scaled
  by ``routed_scaling``;
- per layer it sows ``moe_stats`` (held experts' loads, dropped count)
  into ``intermediates``; ``DistributedModel.moe_stats()`` hands them to
  the step function, which returns them beside the loss, and
  ``record_moe_stats`` reads them back into ``smp_moe_*`` gauges (the
  share of the held experts a chunk visits among them).

The one-hot router's load-balancing auxiliary loss (Switch-style
``E * sum(fraction_routed * mean_gate)``) is sown into the
``intermediates`` collection under ``moe_aux_loss``; callers training with
it add ``module.apply(..., mutable=["intermediates"])`` output, or read it
through ``smp.nn.moe_aux_losses(...)``.
"""

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
import flax.linen as nn

from smdistributed_modelparallel_tpu.backend.topology import EP_AXIS, TP_AXIS
# Shared helpers with the dense MLP path (copies here would silently
# drift): activation table, init, config lookup, residual-stream spec.
from smdistributed_modelparallel_tpu.nn.transformer import (
    _activation,
    _cfg,
    _hidden_spec,
    _init,
)
from smdistributed_modelparallel_tpu.nn.utils import (
    axis_partitioned,
    resolve_deterministic,
    shard_activation,
)
from smdistributed_modelparallel_tpu.utils.exceptions import SMPValidationError


class DistributedMoE(nn.Module):
    """Drop-in MoE replacement for the transformer MLP block.

    Top-k routed mixture of expert FFNs with fixed per-expert capacity
    ``C = ceil(top_k * tokens * capacity_factor / num_experts)``; tokens
    beyond an expert's capacity fall through the residual (standard
    Switch/GShard semantics).
    """

    hidden_size: int
    intermediate_size: int
    num_experts: int
    top_k: int = 2
    capacity_factor: float = 1.25
    activation: str = "gelu"
    hidden_dropout_prob: float = 0.0
    initializer_range: float = 0.02
    router_jitter: float = 0.0
    aux_loss_coef: float = 0.01
    deterministic: Optional[bool] = None
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, hidden):
        if self.top_k < 1 or self.top_k > self.num_experts:
            raise SMPValidationError(
                f"moe top_k ({self.top_k}) must be in [1, num_experts="
                f"{self.num_experts}]."
            )
        from smdistributed_modelparallel_tpu.backend.state import state

        ep = state.mesh.shape.get(EP_AXIS, 1) if state.initialized else 1
        if ep > 1 and self.num_experts % ep != 0:
            raise SMPValidationError(
                f"num_experts ({self.num_experts}) must be divisible by "
                f"expert_parallel_degree ({ep}) so experts shard evenly "
                "over the ep mesh axis."
            )
        D, F, E, K = (
            self.hidden_size, self.intermediate_size, self.num_experts,
            self.top_k,
        )
        dtype = self.dtype or hidden.dtype
        init = _init(self.initializer_range)
        deterministic = resolve_deterministic(self.deterministic)

        B, T = hidden.shape[0], hidden.shape[1]
        N = B * T
        x = hidden.reshape(N, D)

        # ---- router (fp32 for a stable softmax) -----------------------
        router_kernel = self.param("router/kernel", init, (D, E), jnp.float32)
        logits = x.astype(jnp.float32) @ router_kernel
        if self.router_jitter > 0.0 and not deterministic:
            noise = jax.random.uniform(
                self.make_rng("dropout"), logits.shape,
                minval=1.0 - self.router_jitter,
                maxval=1.0 + self.router_jitter,
            )
            logits = logits * noise
        gates = jax.nn.softmax(logits, axis=-1)            # [N, E]

        gate_vals, expert_idx = jax.lax.top_k(gates, K)    # [N, K]
        if K > 1:
            # Renormalize so the combine is a convex mixture. NOT for k=1:
            # Switch-style top-1 must scale by the raw softmax probability —
            # g/g == 1 would starve the router of task-loss gradient.
            gate_vals = gate_vals / jnp.maximum(
                jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
            )

        capacity = int(max(K, -(-K * N * self.capacity_factor // E)))

        # Position of each assignment within its expert, ordered k-major
        # (all first choices before any second choice) then token-major —
        # first choices are never dropped in favor of second choices.
        # Bookkeeping in int32: a float32 cumsum stops representing
        # consecutive integers past 2^24 assignments and would silently
        # collide capacity slots at pod-scale batches.
        sel = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)  # [N, K, E]
        sel_i = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)
        sel_km = sel_i.transpose(1, 0, 2).reshape(K * N, E)
        pos_km = jnp.cumsum(sel_km, axis=0) - sel_km
        pos = pos_km.reshape(K, N, E).transpose(1, 0, 2)        # [N, K, E]
        pos_k = jnp.sum(pos * sel_i, axis=-1)                   # [N, K] int32
        keep = (pos_k < capacity).astype(jnp.float32)

        pos_oh = jax.nn.one_hot(pos_k, capacity, dtype=jnp.float32)
        # combine[n, e, c]: gate weight of token n's assignment to slot
        # (e, c); dispatch is its 0/1 support.
        combine = jnp.einsum("nk,nke,nkc->nec", gate_vals * keep, sel, pos_oh)
        dispatch = jnp.einsum("nk,nke,nkc->nec", keep, sel, pos_oh)

        # ---- load-balance auxiliary (Switch eq. 4) --------------------
        frac_routed = jnp.mean(sel[:, 0, :], axis=0)       # top-1 fractions
        mean_gate = jnp.mean(gates, axis=0)
        aux = jnp.asarray(E, jnp.float32) * jnp.sum(frac_routed * mean_gate)
        self.sow("intermediates", "moe_aux_loss", self.aux_loss_coef * aux)

        # ---- expert FFNs (batched over the ep-sharded expert axis) ----
        fc_kernel = self.param(
            "fc/kernel", axis_partitioned(init, (EP_AXIS, None, TP_AXIS)),
            (E, D, F), dtype,
        )
        fc_bias = self.param(
            "fc/bias", axis_partitioned(nn.initializers.zeros, (EP_AXIS, TP_AXIS)),
            (E, F), dtype,
        )
        proj_kernel = self.param(
            "proj/kernel", axis_partitioned(init, (EP_AXIS, TP_AXIS, None)),
            (E, F, D), dtype,
        )
        proj_bias = self.param(
            "proj/bias", axis_partitioned(nn.initializers.zeros, (EP_AXIS, None)),
            (E, D), dtype,
        )

        expert_in = jnp.einsum(
            "nec,nd->ecd", dispatch.astype(hidden.dtype), x
        )
        expert_in = shard_activation(expert_in, EP_AXIS, None, None)
        h = jnp.einsum("ecd,edf->ecf", expert_in, fc_kernel.astype(expert_in.dtype))
        h = shard_activation(h, EP_AXIS, None, TP_AXIS)
        h = _activation(self.activation)(h + fc_bias[:, None].astype(h.dtype))
        y = jnp.einsum("ecf,efd->ecd", h, proj_kernel.astype(h.dtype))
        y = y + proj_bias[:, None].astype(y.dtype)
        y = shard_activation(y, EP_AXIS, None, None)

        out = jnp.einsum("nec,ecd->nd", combine.astype(y.dtype), y)
        out = out.reshape(B, T, D)
        # Residual-stream layout matches the dense MLP it replaces (incl.
        # the optimize='memory' sequence-parallel sharding).
        memory_opt = _cfg("optimize", "speed") == "memory"
        out = shard_activation(out, *_hidden_spec(memory_opt))
        if self.hidden_dropout_prob > 0.0 and not deterministic:
            out = nn.Dropout(self.hidden_dropout_prob, deterministic=False)(out)
        return out


def collect_moe_aux(intermediates):
    """Sum every sown ``moe_aux_loss`` in an intermediates tree, or None
    when nothing was sown (so MoE-free models add no term to traced
    losses). One entry per MoE layer; scanned stacks sow a [num_layers]
    vector."""
    if not intermediates:
        return None
    total = None
    for path, leaf in jax.tree_util.tree_leaves_with_path(intermediates):
        if any(
            getattr(k, "key", None) == "moe_aux_loss" for k in path
        ):
            s = jnp.sum(leaf)
            total = s if total is None else total + s
    return total


def moe_aux_losses(intermediates):
    """Sum every ``moe_aux_loss`` sown anywhere in an intermediates tree
    (0.0 when none). Kept for users reading aux losses from their own
    ``module.apply(..., mutable=["intermediates"])`` calls; the standard
    ``DistributedModel`` / pipeline paths fold the aux loss into the
    differentiated step loss automatically (weighted by the
    ``moe_aux_loss_weight`` config key)."""
    total = collect_moe_aux(intermediates)
    return 0.0 if total is None else total


# ----------------------------------------------------------------------
# Dropless: sort by expert, grouped matrix product over the experts held
# ----------------------------------------------------------------------


# Sorted rows a grouped product takes at a time, at least: large enough to
# fill the MXU over a handful of experts, small enough that the loop's work
# follows the rows that landed here. Not tuned per model; tests shrink it.
ROWS_PER_CHUNK = 1024


def _chunk_rows(tokens, top_k, count, experts):
    """Rows a chunk: ``ROWS_PER_CHUNK``, in as many multiples as make an
    even router's load here (``tokens x top_k x count / experts``) about
    three chunks, and a multiple more for as long as that load would end
    exactly on a chunk's edge. A chunk costs much the same full or nearly
    empty (each one's weight gradients are [count, D, 2F] and [count, F, D]
    whatever its rows), so a share whose even load is a whole number of
    chunks (8 of 64 a token on 16 held over 8,192 tokens: 16 x 1,024; 4
    of 64 on 8 held: 2 x 2,048; the same over 4,096 tokens: 2,048 again
    after one multiple more, hence as long as) would run or skip one more,
    nearly empty chunk on each call by the draw of the weights, and the
    step's time would follow the draw (2.0 us a row at 4 of 64 on 8 held,
    PERF.md PR 42; 2.09 over 4,096 tokens, PR 44), and the first pays
    sixteen chunks' fixed cost where three carry the rows. Three chunks, or
    two of the next size, keep the loop's work within a third of the even
    load of the rows that landed here."""
    even = tokens * top_k * count // experts
    multiples = max(1, -(-even // (3 * ROWS_PER_CHUNK)))
    while even and even % (multiples * ROWS_PER_CHUNK) == 0:
        multiples += 1
    return ROWS_PER_CHUNK * multiples


def _activated(h, valid, activation, dtype):
    """``act(gate) * up`` of the first product, masked: [R, F]."""
    gate, up = jnp.split(jnp.where(valid, h, 0), 2, axis=-1)
    return (_activation(activation)(gate) * up).astype(dtype)


def _weighted(y, weights, valid):
    """The second product, masked, times the rows' combine weights."""
    return jnp.where(valid, y, 0).astype(jnp.float32) * jnp.where(
        valid[:, 0], weights, 0.0)[:, None]


def _valid_rows(rows, group_sizes):
    return (jnp.arange(rows) < jnp.sum(group_sizes))[:, None]


def _expert_ffn(rows, w_gate_up, w_down, group_sizes, activation):
    """``(E_e(rows), valid)`` for sorted ``rows`` [R, D] whose first
    ``sum(group_sizes)`` rows belong, group by group, to the held experts:
    the second product [R, D] in the rows' dtype and the mask of the rows
    that belong. ``w_gate_up`` [n, D, 2F], ``w_down`` [n, F, D]. A grouped
    product leaves the rows past its groups as the memory was (on the
    chip: anything), so they are masked going in and in the middle, and
    whoever sums the result masks them coming out (``_weighted``, or the
    kernel that adds the rows that belong and no other): nothing of them
    reaches a result. The backward chain (``_chunk_grads``) masks alike."""
    valid = _valid_rows(rows.shape[0], group_sizes)
    h = jax.lax.ragged_dot(jnp.where(valid, rows, 0), w_gate_up, group_sizes)
    h = _activated(h, valid, activation, rows.dtype)
    return jax.lax.ragged_dot(h, w_down, group_sizes), valid


def _wgrad_kernel_engages(rows, w_gate_up, w_down):
    """Whether ``_held_bwd`` sums the weight gradients inside
    ``ops/pallas_grouped_wgrad`` (on the TPU, whole row tiles a chunk,
    lane-aligned widths, the weights whole on one device: a Mosaic call
    cannot be partitioned over a mesh) or takes the grouped products'
    transposes and adds them up. From what the trace can see; no knob."""
    from smdistributed_modelparallel_tpu.ops.pallas_grouped_wgrad import (
        grouped_wgrad_ok,
    )

    _, D, F2 = w_gate_up.shape
    return (_on_one_device()
            and w_gate_up.dtype == w_down.dtype
            and grouped_wgrad_ok(rows, D, F2)
            and grouped_wgrad_ok(rows, F2 // 2, D))


def _on_one_device():
    from smdistributed_modelparallel_tpu.backend.state import state

    return not (state.initialized and state.mesh.devices.size > 1)


def _combine_kernel_engages(x, rows):
    """Whether a chunk's rows are summed back to their tokens inside
    ``ops/pallas_row_scatter_add`` (on the TPU, one device, a lane-aligned
    width, whole row blocks a chunk, a tile that divides the tokens, and
    rows enough for a stream of the whole sum to pay: its conditions are
    not the weight gradients' kernel's, hence a predicate of its own) or
    by XLA's scatter-add, forward and backward alike. ``x`` [N, D]: the
    layer's tokens; the rows are of its dtype. From what the trace can
    see; no knob."""
    from smdistributed_modelparallel_tpu.ops.pallas_row_scatter_add import (
        row_scatter_add_ok,
    )

    return _on_one_device() and row_scatter_add_ok(
        *x.shape, rows, x.dtype.itemsize)


def _chunk_grads(picked, g_picked, w, sizes, w_gate_up, w_down, wt_gate_up,
                 wt_down, activation, sums):
    """One chunk of ``_held_bwd``, the chain written out: the gradients of
    ``sum(_weighted(E(picked), w) * g)`` for the rows [R, D] and the
    combine weights [R], and the running fp32 sums ``(dgu, dd)`` of the
    weights' with this chunk's added. ``g_picked`` [R, D]: the output's
    cotangent at the chunk's tokens, in the rows' dtype; ``wt_gate_up``
    [n, 2F, D] and ``wt_down`` [n, D, F]: the weights transposed, made by
    the caller once a layer call.

    Three grouped products over rows, all in forward form: ``h`` (the
    first product, run again), ``u = g @ w_down^T`` and ``dr = d_h @
    w_gate_up^T``. The second product ``y = h_act @ w_down`` is not formed:
    its cotangent is ``d_y = w * g`` whatever ``y`` holds, and its one
    other reader, the combine weights' gradient ``sum_d y * g``, is
    ``sum_f h_act * u`` by the same sum taken in the other order. Rows
    past the groups are masked going in (``picked``, ``g_picked``, ``w``),
    in the middle (``_activated`` and its transpose) and coming out (``u``,
    ``dr``): nothing of them reaches a gradient."""
    dgu, dd = sums
    dtype = picked.dtype
    valid = _valid_rows(picked.shape[0], sizes)
    x_m = jnp.where(valid, picked, 0)
    g_m = jnp.where(valid, g_picked, 0)
    wv = jnp.where(valid[:, 0], w, 0.0)[:, None]
    h = jax.lax.ragged_dot(x_m, w_gate_up, sizes)
    h_act, act_vjp = jax.vjp(
        lambda h: _activated(h, valid, activation, dtype), h)
    u = jnp.where(valid, jax.lax.ragged_dot(
        g_m, wt_down, sizes, preferred_element_type=jnp.float32), 0)
    dwc = jnp.sum(h_act.astype(jnp.float32) * u, axis=-1)
    d_y = (wv * g_m).astype(dtype)
    d_h, = act_vjp((wv * u).astype(dtype))
    dr = jnp.where(valid, jax.lax.ragged_dot(d_h, wt_gate_up, sizes), 0)
    if _wgrad_kernel_engages(picked.shape[0], w_gate_up, w_down):
        from smdistributed_modelparallel_tpu.ops.pallas_grouped_wgrad import (
            grouped_wgrad,
        )

        dgu = grouped_wgrad(x_m, d_h, sizes, dgu)
        dd = grouped_wgrad(h_act, d_y, sizes, dd)
    else:
        # The grouped products' transposes for the weight alone: a
        # [held, D, 2F] and a [held, F, D] product in the operands' dtype.
        da, = jax.linear_transpose(
            lambda a: jax.lax.ragged_dot(x_m, a, sizes), w_gate_up)(d_h)
        db, = jax.linear_transpose(
            lambda b: jax.lax.ragged_dot(h_act, b, sizes), w_down)(d_y)
        dgu = dgu + da.astype(jnp.float32)
        dd = dd + db.astype(jnp.float32)
    return dr, dwc, (dgu, dd)


def _chunk(c, tokens, weights, offsets, rows):
    """Chunk ``c`` of the sorted assignments: its tokens and combine
    weights [rows], and how many of its rows each held expert owns."""
    start = c * rows
    group_sizes = (jnp.clip(offsets[1:] - start, 0, rows)
                   - jnp.clip(offsets[:-1] - start, 0, rows))
    return (jax.lax.dynamic_slice(tokens, (start,), (rows,)),
            jax.lax.dynamic_slice(weights, (start,), (rows,)),
            group_sizes)


def _used_chunks(offsets, rows):
    return (offsets[-1] + rows - 1) // rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def held_experts_output(x, w_gate_up, w_down, weights, tokens, offsets,
                        activation, rows):
    """What the held experts add to each token: [N, D] float32.

    ``tokens`` / ``weights`` [A]: the token and combine weight of each
    assignment, sorted by held expert (``offsets`` [n + 1] are the
    experts' row ranges; assignments to experts elsewhere come last and
    are never read). Runs ``ceil(offsets[-1] / rows)`` chunks of ``rows``
    assignments: gather the chunk's token rows, the grouped gated FFN,
    scatter-add into the output. The loop's trip count is data, so the
    backward pass is written out (``_held_bwd``) and runs the same chunks
    again."""
    out, _ = _held_fwd(x, w_gate_up, w_down, weights, tokens, offsets,
                       activation, rows)
    return out


def _held_fwd(x, w_gate_up, w_down, weights, tokens, offsets, activation,
              rows):
    from smdistributed_modelparallel_tpu.ops.pallas_row_scatter_add import (
        row_scatter_add,
    )

    kernel = _combine_kernel_engages(x, rows)

    def body(c, out):
        with jax.named_scope("smp/moe/dispatch"):
            t, w, sizes = _chunk(c, tokens, weights, offsets, rows)
            picked = x[t]
        with jax.named_scope("smp/moe/experts"):
            y, valid = _expert_ffn(picked, w_gate_up, w_down, sizes,
                                   activation)
        with jax.named_scope("smp/moe/combine"):
            if kernel:
                return row_scatter_add(out, y, t, sizes, w)
            return out.at[t].add(_weighted(y, w, valid))

    out = jax.lax.fori_loop(
        0, _used_chunks(offsets, rows), body,
        jnp.zeros(x.shape, jnp.float32))
    return out, (x, w_gate_up, w_down, weights, tokens, offsets)


def _held_bwd(activation, rows, res, g):
    from smdistributed_modelparallel_tpu.ops.pallas_row_scatter_add import (
        row_scatter_add,
    )

    x, w_gate_up, w_down, weights, tokens, offsets = res
    kernel = _combine_kernel_engages(x, rows)
    with jax.named_scope("smp/moe/experts"):
        # Once a layer call: every grouped product of a chunk then reads
        # its weight in forward form, and the loop re-lays nothing.
        wt_gate_up = jnp.swapaxes(w_gate_up, 1, 2)
        wt_down = jnp.swapaxes(w_down, 1, 2)
        # ``g`` enters the chain as an operand of the rows' dtype only.
        g = g.astype(x.dtype)

    def body(c, carry):
        dx, dgu, dd, dw = carry
        with jax.named_scope("smp/moe/dispatch"):
            t, w, sizes = _chunk(c, tokens, weights, offsets, rows)
            picked, g_picked = x[t], g[t]
        with jax.named_scope("smp/moe/experts"):
            dr, dwc, (dgu, dd) = _chunk_grads(
                picked, g_picked, w, sizes, w_gate_up, w_down, wt_gate_up,
                wt_down, activation, (dgu, dd))
        with jax.named_scope("smp/moe/combine"):
            dx = (row_scatter_add(dx, dr, t, sizes) if kernel
                  else dx.at[t].add(dr.astype(jnp.float32)))
            return (dx, dgu, dd,
                    jax.lax.dynamic_update_slice(dw, dwc, (c * rows,)))

    dx, dgu, dd, dw = jax.lax.fori_loop(
        0, _used_chunks(offsets, rows), body,
        (jnp.zeros(x.shape, jnp.float32),
         jnp.zeros(w_gate_up.shape, jnp.float32),
         jnp.zeros(w_down.shape, jnp.float32),
         jnp.zeros(weights.shape, jnp.float32)))
    no_grad = lambda a: np.zeros(a.shape, jax.dtypes.float0)  # noqa: E731
    return (dx.astype(x.dtype), dgu.astype(w_gate_up.dtype),
            dd.astype(w_down.dtype), dw, no_grad(tokens), no_grad(offsets))


held_experts_output.defvjp(_held_fwd, _held_bwd)


def _row_buffer(tokens, top_k, count, rows):
    """Rows of the sorted-assignment buffer: the worst case, every token on
    every held expert it could reach, rounded up to whole chunks."""
    return -(-tokens * min(top_k, count) // rows) * rows


def route_to_held(top_idx, top_weight, first, count, rows):
    """Sort the [N, K] assignments by held expert.

    Returns ``tokens`` and ``weights`` [``_row_buffer``], ``offsets``
    [count + 1], and the counters: ``loads`` [count] and ``dropped``
    (assignments to a held expert that the buffer has no row for: 0 under
    ``_row_buffer``'s bound, and what a smaller buffer would lose)."""
    N, K = top_idx.shape
    here = (top_idx >= first) & (top_idx < first + count)
    # ``count`` marks an expert held elsewhere: it sorts after every held one.
    expert = jnp.where(here, top_idx - first, count).reshape(-1)
    order = jnp.argsort(expert, stable=True)
    loads = jnp.sum(
        expert[:, None] == jnp.arange(count)[None, :], axis=0, dtype=jnp.int32)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(loads)])
    buffer = _row_buffer(N, K, count, rows)
    order = jnp.pad(order, (0, max(0, buffer - N * K)))[:buffer]
    landed = jnp.arange(buffer) < offsets[-1]
    tokens = jnp.where(landed, order // K, 0).astype(jnp.int32)
    weights = jnp.where(landed, top_weight.reshape(-1)[order], 0.0)
    dropped = offsets[-1] - jnp.minimum(offsets[-1], buffer)
    return tokens, weights, offsets, loads, dropped


class DistributedDroplessMoE(nn.Module):
    """Expert MLP block that drops no token: top-k routing over
    ``num_experts``, a grouped matrix product over the experts ``held``
    here, a shared expert, renormalised and scaled top-k weights. See the
    module docstring."""

    hidden_size: int
    intermediate_size: int            # an expert's width
    num_experts: int                  # the router's width (published count)
    top_k: int = 2
    held: Optional[tuple] = None      # (first, count); None: all of them
    shared_intermediate_size: int = 0
    norm_topk: bool = True
    routed_scaling: float = 1.0
    # The router's scoring law: "softmax" over all ``num_experts`` outputs,
    # or "sigmoid" of each (LFM2's: the chosen scores renormalised over
    # their sum + 1e-6, as its class writes it) ...
    score: str = "softmax"
    # ... and a per-expert bias that enters the selection and not the
    # weights (``router/selection_bias`` [num_experts], float32): the k
    # largest of score + bias are chosen, the weights are their scores.
    # A leaf the training step leaves as loaded: no gradient reaches it
    # and ``DistributedOptimizer`` gives it no update
    # (``nn/utils.FIXED_PARAM_NAMES``); whoever balances the load sets it
    # between steps.
    selection_bias: bool = False
    activation: str = "silu"
    initializer_range: float = 0.02
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, hidden):
        from smdistributed_modelparallel_tpu.backend.state import state
        from smdistributed_modelparallel_tpu.nn.transformer import (
            DistributedTransformerOutputLayer,
        )

        D, F, E, K = (self.hidden_size, self.intermediate_size,
                      self.num_experts, self.top_k)
        first, count = self.held if self.held is not None else (0, E)
        if not (1 <= K <= E and 0 <= first and first + count <= E
                and count >= 1):
            raise SMPValidationError(
                f"dropless moe: top_k {K} of num_experts {E}, held "
                f"({first}, {count}) must lie inside them."
            )
        if self.score not in ("softmax", "sigmoid"):
            raise SMPValidationError(
                f"dropless moe: score {self.score!r} is neither 'softmax' "
                "nor 'sigmoid'."
            )
        if state.initialized and state.mesh.shape.get(EP_AXIS, 1) > 1:
            raise SMPValidationError(
                "DistributedDroplessMoE computes one expert-parallel rank's "
                "share; the exchange between ranks (expert_parallel_degree "
                "> 1) is not written yet. Use DistributedMoE over ep."
            )
        dtype = self.dtype or hidden.dtype
        init = _init(self.initializer_range)
        B, T = hidden.shape[0], hidden.shape[1]
        x = hidden.reshape(B * T, D)

        with jax.named_scope("smp/moe/route"):
            router_kernel = self.param(
                "router/kernel", init, (D, E), jnp.float32)
            logits = jnp.dot(
                x.astype(jnp.float32), router_kernel,
                precision=jax.lax.Precision.HIGHEST)
            sigmoid = self.score == "sigmoid"
            probs = (jax.nn.sigmoid(logits) if sigmoid
                     else jax.nn.softmax(logits, axis=-1))
            if self.selection_bias:
                bias = self.param(
                    "router/selection_bias", nn.initializers.zeros, (E,),
                    jnp.float32)
                _, top_idx = jax.lax.top_k(
                    probs + jax.lax.stop_gradient(bias), K)
                top_weight = jnp.take_along_axis(probs, top_idx, axis=-1)
            else:
                top_weight, top_idx = jax.lax.top_k(probs, K)
            if self.norm_topk:
                total = jnp.sum(top_weight, axis=-1, keepdims=True)
                top_weight = top_weight / (total + 1e-6 if sigmoid else total)
            top_weight = top_weight * self.routed_scaling
        rows = _chunk_rows(B * T, K, count, E)
        with jax.named_scope("smp/moe/dispatch"):
            tokens, weights, offsets, loads, dropped = route_to_held(
                top_idx, top_weight, first, count, rows)
        self.sow("intermediates", "moe_stats",
                 jnp.concatenate([loads, dropped[None]]))

        gate_up = self.param(
            "experts/gate_up/kernel", init, (count, D, 2, F), dtype)
        down = self.param("experts/down/kernel", init, (count, F, D), dtype)
        w_gate_up = gate_up.astype(x.dtype).reshape(count, D, 2 * F)
        w_down = down.astype(x.dtype)
        _record_trace("/".join(self.path), rows,
                      _wgrad_kernel_engages(rows, w_gate_up, w_down),
                      _combine_kernel_engages(x, rows),
                      int(sigmoid and self.selection_bias))
        # Its own scopes inside: gather, grouped FFN, scatter-add.
        out = held_experts_output(
            x, w_gate_up, w_down, weights, tokens, offsets,
            self.activation, rows).reshape(B, T, D)
        if self.shared_intermediate_size:
            with jax.named_scope("smp/moe/shared"):
                shared = DistributedTransformerOutputLayer(
                    hidden_size=D,
                    intermediate_size=self.shared_intermediate_size,
                    hidden_dropout_prob=0.0, activation=self.activation,
                    initializer_range=self.initializer_range,
                    use_mlp_bias=False, gated_mlp=True, dtype=self.dtype,
                    name="shared",
                )(hidden)
        with jax.named_scope("smp/moe/combine"):
            if self.shared_intermediate_size:
                out = out + shared.astype(jnp.float32)
            out = out.astype(hidden.dtype)
        memory_opt = _cfg("optimize", "speed") == "memory"
        return shard_activation(out, *_hidden_spec(memory_opt))


# Rows a chunk of each expert layer traced so far, by the layer's path (the
# key ``collect_moe_stats`` gives its counters): ``record_moe_stats`` cuts
# the recorded loads into the chunks the step ran.
_TRACED_CHUNK_ROWS = {}


def _record_trace(layer, rows, wgrad_engaged, combine_engaged, router_law):
    from smdistributed_modelparallel_tpu.utils.telemetry import telemetry

    _TRACED_CHUNK_ROWS[layer] = rows
    telemetry.gauge(
        "smp_moe_router_law",
        "the expert layer's routing law: 0 softmax scores and their k "
        "largest, 1 sigmoid scores with a bias in the selection; set while "
        "the layer is traced",
    ).labels(layer=layer).set(router_law)
    telemetry.gauge(
        "smp_moe_wgrad_kernel_engaged",
        "1 where the expert layer's weight gradients are summed inside the "
        "grouped_wgrad kernel, 0 where the grouped products' transposes are "
        "converted and added; set while the layer is traced",
    ).labels(layer=layer).set(int(wgrad_engaged))
    telemetry.gauge(
        "smp_moe_combine_kernel_engaged",
        "1 where the expert layer's routed rows are summed back to their "
        "tokens inside the row_scatter_add kernel, forward and backward, 0 "
        "where XLA's scatter-add does it; set while the layer is traced",
    ).labels(layer=layer).set(int(combine_engaged))


def _experts_visited(loads, rows):
    """``(visits, chunks x held)`` of one layer call: over the chunks of
    ``rows`` sorted rows that the held experts' ``loads`` fill, how many
    (chunk, expert) pairs share a row."""
    offsets = np.concatenate([[0], np.cumsum(loads)])
    first = np.arange(-(-offsets[-1] // rows))[:, None] * rows
    shares = (np.minimum(offsets[1:], first + rows)
              > np.maximum(offsets[:-1], first))
    return int(shares.sum()), shares.size


def collect_moe_stats(intermediates):
    """``{layer path: stats}`` of every sown ``moe_stats`` in an
    intermediates tree: int32 [..., count + 1] (a leading axis for each
    scan the layer sits in), the held experts' loads and then the dropped
    count. ``{}`` when nothing was sown."""
    found = {}
    if not intermediates:
        return found
    for path, leaf in jax.tree_util.tree_leaves_with_path(intermediates):
        keys = [getattr(k, "key", None) for k in path]
        if "moe_stats" in keys:
            found["/".join(k for k in keys[:keys.index("moe_stats")]
                           if isinstance(k, str))] = leaf
    return found


def record_moe_stats(stats):
    """Read a step's ``moe_stats`` back (a host transfer: call it outside
    a timed path) into ``smp_moe_local_assignments`` (the assignments that
    landed on held experts, summed over layers and microbatches),
    ``smp_moe_dropped_assignments``, for each expert layer
    ``smp_moe_expert_load_max_over_mean{layer}``, and
    ``smp_moe_wgrad_experts_visited_share``: over every chunk of every
    layer call recorded, experts with rows in the chunk / held experts,
    which is the share of the weight gradients' blocks the backward pass
    reads and writes (layers traced in this process; None without one).
    ``stats``: what the step function returned from ``model.moe_stats()``
    (arrays or the ``StepOutput`` holding them). Returns ``{"local",
    "dropped", "max_over_mean": {layer: value}, "wgrad_visited_share"}``."""
    from smdistributed_modelparallel_tpu.utils.telemetry import telemetry

    if hasattr(stats, "stack"):
        stats = stats.stack()
    local, dropped, ratios = 0, 0, {}
    visits = pairs = 0
    for path, leaf in sorted(stats.items()):
        leaf = np.asarray(leaf)
        count = leaf.shape[-1] - 1
        if path in _TRACED_CHUNK_ROWS:
            for call in leaf.reshape(-1, count + 1):
                v, p = _experts_visited(call[:count], _TRACED_CHUNK_ROWS[path])
                visits, pairs = visits + v, pairs + p
        # [microbatches, (scans ...,) layers of this run, count + 1]
        per_layer = leaf.reshape(leaf.shape[0], -1, count + 1).sum(axis=0)
        for i, row in enumerate(per_layer):
            loads = row[:count].astype(np.float64)
            local += int(loads.sum())
            dropped += int(row[count])
            ratios[f"{path}#{i}"] = (
                float(loads.max() / loads.mean()) if loads.sum() else 0.0)
    telemetry.gauge(
        "smp_moe_local_assignments",
        "token-expert assignments of the last recorded step that landed on "
        "experts held here (all expert layers, all microbatches)",
    ).set(local)
    telemetry.gauge(
        "smp_moe_dropped_assignments",
        "assignments to a held expert the last recorded step did not "
        "compute (the dropless layer's bound makes this 0)",
    ).set(dropped)
    ratio_gauge = telemetry.gauge(
        "smp_moe_expert_load_max_over_mean",
        "largest held expert's load over the mean load, per expert layer, "
        "last recorded step",
    )
    for layer, value in ratios.items():
        ratio_gauge.labels(layer=layer).set(value)
    share = visits / pairs if pairs else None
    if share is not None:
        telemetry.gauge(
            "smp_moe_wgrad_experts_visited_share",
            "experts with rows in a chunk / held experts, over the chunks "
            "of the last recorded step: the share of the weight gradients' "
            "blocks the backward pass reads and writes",
        ).set(share)
    return {"local": local, "dropped": dropped, "max_over_mean": ratios,
            "wgrad_visited_share": share}
