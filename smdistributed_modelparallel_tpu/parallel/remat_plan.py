"""Memory-budgeted recompute planner.

Decides, per (stage, chunk, pass), whether a pipeline backward pass
re-runs the chunk forward (activation recomputation — the seed behavior)
or reads stashed ``jax.vjp`` residuals captured by an earlier pass. The
knob (config ``recompute``, env alias ``SMP_RECOMPUTE``):

- ``"full"``    — recompute everywhere (the untouched old code path of
  every executor; a checkpointed layer keeps the flash forward kernel's
  two outputs, as in every mode: ``memory.remat_policy``).
- ``"stash_weight"`` — zero-bubble only: the B (input-grad) pass captures
  per-layer vjp residuals + per-layer output cotangents into stash rings
  sized by ``memory.recompute_ring_plan``; the deferred W (weight-grad)
  pass consumes them instead of re-running the chunk forward — the
  schedule's double-forward drops to a single forward per microbatch.
- ``"stash_all"`` — additionally capture residuals at the FORWARD pass so
  the B pass consumes them too (no backward-time forward at all); on the
  interleaved/1F1B executors (which have no W pass) this is the only
  stashing mode and removes the B recompute.
- ``"auto"``    — target the strongest stash the schedule supports, but
  budget the stash bytes against ``SMP_RECOMPUTE_BUDGET_MB`` (config
  ``recompute_budget_mb``; default: the XLA memory-breakdown temp bytes
  of the last audited program, else the ring-plan bound) and degrade
  per-(stage, chunk) back to recompute, highest chunk first, until the
  plan fits.

The plan is logged, published as ``smp_recompute_*`` gauges, recorded for
the compiled-program fingerprint (``utils/hlo_audit`` stamps a
``recompute`` block when a non-default plan is active), and
machine-checked by the extended ring plan: stash ring slots in the
executor equal the planner's prediction, and an ``auto`` plan never
exceeds its budget.

Non-pipeline paths (pp=1 microbatch scan, fill-drain) have no schedule
to plan over; there the knob maps onto ``jax.checkpoint`` policies in
``parallel/memory.remat_policy`` (``dots_with_no_batch_dims_saveable``
family), trading the same memory for the same FLOPs one level down.
"""

import os

from smdistributed_modelparallel_tpu.utils.logger import get_logger

logger = get_logger()

MODES = ("full", "stash_weight", "stash_all", "auto")
ENV = "SMP_RECOMPUTE"
BUDGET_ENV = "SMP_RECOMPUTE_BUDGET_MB"

#: Latest plan per schedule kind ("zb" / "1f1b") — read by the HLO-audit
#: fingerprint (``recompute`` block) and the telemetry report.
plans = {}


def resolve(cfg=None):
    """The effective knob value ("full" when unset/uninitialized)."""
    if cfg is None:
        try:
            from smdistributed_modelparallel_tpu.backend.state import state

            cfg = state.cfg
        except Exception:
            cfg = None
    mode = getattr(cfg, "recompute", None) if cfg is not None else None
    if mode is None:
        mode = os.environ.get(ENV, "full").strip().lower() or "full"
    if mode not in MODES:
        logger.warning("Unknown recompute mode %r; using 'full'.", mode)
        return "full"
    return mode


def budget_bytes(cfg=None):
    """The auto-mode stash budget in bytes, or None for "unbudgeted":
    config ``recompute_budget_mb`` (env ``SMP_RECOMPUTE_BUDGET_MB``),
    else the XLA memory-breakdown temp bytes of the last audited program
    (headroom the program already spends on temporaries), else None —
    the planner then falls back to its own ring-plan bound (stash
    everything the rings can hold)."""
    mb = getattr(cfg, "recompute_budget_mb", None) if cfg is not None else None
    if mb is None:
        env = os.environ.get(BUDGET_ENV)
        if env:
            try:
                mb = int(env)
            except ValueError:
                logger.warning("%s=%r is not an integer; ignored.",
                               BUDGET_ENV, env)
    if mb is not None:
        return int(mb) * (1 << 20)
    try:
        from smdistributed_modelparallel_tpu.utils import hlo_audit

        best = None
        for audit in hlo_audit.audits.values():
            tmp = (audit.memory or {}).get("temp_bytes")
            if tmp:
                best = int(tmp)
        if best:
            return best
    except Exception:
        pass
    return None


# Static executed-FLOP recompute model, in forward-equivalents per
# (chunk, microbatch) unit (fwd = dgrad = wgrad = 1 — the matmul classes
# cost the same): which passes run a forward / a dgrad chain / a wgrad,
# and how much of the executed dot work is recomputation. This is the
# planner's *executed* prediction; the X-ray remat census measures the
# compiled program's *structural* duplication, which additionally counts
# per-segment body copies — the census is the gate, this is the model.
_EXEC_MODEL = {
    # schedule -> mode -> (executed_units, recomputed_units)
    "zb": {
        "full": (6.0, 3.0),          # F:f  B:f+d  W:f+d+w
        "stash_weight": (4.0, 1.0),  # F:f  B:f+d  W:w
        "stash_all": (3.0, 0.0),     # F:f(capture)  B:d  W:w
    },
    "1f1b": {
        "full": (4.0, 1.0),          # F:f  B:f+d+w
        "stash_all": (3.0, 0.0),     # F:f(capture)  B:d+w
    },
}


def predicted_fraction(schedule, mode):
    """Executed-FLOP recompute fraction of the schedule under `mode`
    (None when the mode doesn't apply to the schedule)."""
    ent = _EXEC_MODEL.get(schedule, {}).get(mode)
    if ent is None:
        return None
    executed, recomputed = ent
    return recomputed / executed if executed else 0.0


def active_for(cfg):
    """The recompute block the HLO-audit fingerprint stamps for a
    program compiled under `cfg`, or None at the default knob (so
    default fingerprints — and every committed pre-knob golden — are
    byte-identical). Volatile fields (the budget default can come from
    the previous audit's memory breakdown) are excluded; the plan's
    DECISIONS (stash set, ring sizes, bytes) are what gate drift."""
    mode = resolve(cfg)
    if cfg is None or mode == "full":
        return None
    if int(getattr(cfg, "pipeline_parallel_degree", 1) or 1) <= 1:
        # Non-pipeline program: the knob maps onto a jax.checkpoint
        # policy (memory.remat_policy) — no ring plan to report.
        return {"mode": mode, "effective": "checkpoint_policy"}
    sched = ("zb" if getattr(cfg, "pipeline", "") == "zero_bubble"
             else "1f1b")
    p = plans.get(sched)
    if p is None:
        return {"mode": mode, "effective": "unplanned"}
    d = p.as_dict()
    d.pop("budget_bytes", None)
    return d


class RecomputePlan:
    """One resolved stash plan for one pipeline schedule build."""

    def __init__(self, schedule, mode, num_stages, virtual,
                 res_ring_slots, cot_ring_slots,
                 res_slot_bytes, cot_slot_bytes, budget=None):
        self.schedule = schedule          # "zb" | "1f1b"
        self.mode = mode                  # requested knob value
        self.num_stages = int(num_stages)
        self.virtual = int(virtual)
        self.res_ring_slots = int(res_ring_slots)
        self.cot_ring_slots = int(cot_ring_slots)
        self.res_slot_bytes = int(res_slot_bytes)
        self.cot_slot_bytes = int(cot_slot_bytes)
        self.budget_bytes = budget
        # Per-LOCAL-chunk decisions, uniform across stages (the SPMD
        # executors act symmetrically per stage; the per-(stage, chunk)
        # grid below expands this for reporting).
        self.stash_chunks = list(range(self.virtual))
        self.degraded_chunks = []
        if mode == "auto" and budget is not None:
            self._degrade_to_budget()

    # -- accounting -----------------------------------------------------

    def chunk_bytes(self):
        """Per-device stash bytes ONE stashed local chunk costs: its
        residual ring column plus its cotangent ring column."""
        return (self.res_ring_slots * self.res_slot_bytes
                + self.cot_ring_slots * self.cot_slot_bytes)

    @property
    def stash_bytes(self):
        """Per-device stash bytes of the planned rings."""
        return len(self.stash_chunks) * self.chunk_bytes()

    @property
    def effective(self):
        """The mode the executor should build: "full" when every chunk
        degraded, else the stash mode the plan realizes."""
        if not self.stash_chunks:
            return "full"
        if self.mode == "auto":
            # auto's target per schedule: 1f1b has only stash_all (no W
            # pass); on zero_bubble auto deliberately picks stash_weight,
            # NOT the stronger stash_all — its B->W rings cost exactly
            # the W-queue depth the deferral already pays, while
            # stash_all's F->W rings are strictly larger. stash_all is
            # an explicit opt-in.
            return "stash_all" if self.schedule == "1f1b" else "stash_weight"
        return self.mode

    def _degrade_to_budget(self):
        per_chunk = self.chunk_bytes()
        while self.stash_chunks and (
            len(self.stash_chunks) * per_chunk > self.budget_bytes
        ):
            # Highest chunk first: late chunks' stashes live shortest in
            # the schedule, so dropping them loses the least overlap.
            self.degraded_chunks.insert(0, self.stash_chunks.pop())

    # -- export ---------------------------------------------------------

    def grid(self):
        """Per-(stage, chunk) decision grid ("stash"/"recompute")."""
        return [
            ["stash" if k in self.stash_chunks else "recompute"
             for k in range(self.virtual)]
            for _ in range(self.num_stages)
        ]

    def as_dict(self):
        return {
            "schedule": self.schedule,
            "mode": self.mode,
            "effective": self.effective,
            "stash_chunks": list(self.stash_chunks),
            "degraded_chunks": list(self.degraded_chunks),
            "res_ring_slots": self.res_ring_slots,
            "cot_ring_slots": self.cot_ring_slots,
            "res_slot_bytes": self.res_slot_bytes,
            "cot_slot_bytes": self.cot_slot_bytes,
            "stash_bytes": self.stash_bytes,
            "budget_bytes": self.budget_bytes,
            "predicted_fraction_full": predicted_fraction(
                self.schedule, "full"
            ),
            "predicted_fraction_planned": predicted_fraction(
                self.schedule, self.effective
            ),
        }

    def summary(self):
        d = self.as_dict()
        return (
            f"recompute plan [{self.schedule}] mode={self.mode} -> "
            f"{d['effective']}: {len(self.stash_chunks)}/{self.virtual} "
            f"chunk(s) stashed ({len(self.degraded_chunks)} degraded), "
            f"rings res x{self.res_ring_slots} + cot x{self.cot_ring_slots}"
            f" = {self.stash_bytes:,} B/device"
            + (f" vs budget {self.budget_bytes:,} B"
               if self.budget_bytes is not None else " (unbudgeted)")
        )


def plan_pipeline(schedule, mode, num_stages, virtual,
                  res_ring_slots, cot_ring_slots,
                  res_slot_bytes, cot_slot_bytes, cfg=None):
    """Build, log, publish, and record the plan for one executor build."""
    budget = budget_bytes(cfg) if mode == "auto" else None
    p = RecomputePlan(
        schedule, mode, num_stages, virtual,
        res_ring_slots, cot_ring_slots, res_slot_bytes, cot_slot_bytes,
        budget=budget,
    )
    logger.info("%s", p.summary())
    publish(p)
    plans[schedule] = p
    return p


def publish(p):
    """smp_recompute_* gauges for the telemetry report."""
    try:
        from smdistributed_modelparallel_tpu.utils.telemetry import telemetry
    except Exception:  # pragma: no cover - defensive
        return
    lab = {"schedule": p.schedule}
    telemetry.gauge(
        "smp_recompute_mode_info",
        "active recompute plan (value 1; mode/effective in labels)",
    ).labels(mode=p.mode, effective=p.effective, **lab).set(1)
    telemetry.gauge(
        "smp_recompute_stash_bytes",
        "per-device bytes of the planned recompute stash rings",
    ).labels(**lab).set(p.stash_bytes)
    if p.budget_bytes is not None:
        telemetry.gauge(
            "smp_recompute_budget_bytes",
            "stash budget the auto recompute plan was held to",
        ).labels(**lab).set(p.budget_bytes)
    chunks = telemetry.gauge(
        "smp_recompute_chunks",
        "local chunks per stage by recompute-plan decision",
    )
    chunks.labels(decision="stash", **lab).set(len(p.stash_chunks))
    chunks.labels(decision="recompute", **lab).set(len(p.degraded_chunks))
    rings = telemetry.gauge(
        "smp_recompute_ring_slots",
        "stash ring slots per (stage, chunk) of the recompute plan",
    )
    rings.labels(ring="residual", **lab).set(p.res_ring_slots)
    rings.labels(ring="cotangent", **lab).set(p.cot_ring_slots)
    for when in ("full", "planned"):
        frac = predicted_fraction(
            p.schedule, "full" if when == "full" else p.effective
        )
        if frac is not None:
            telemetry.gauge(
                "smp_recompute_predicted_fraction",
                "planner's executed-FLOP recompute fraction (static model; "
                "the X-ray census measures the compiled program)",
            ).labels(when=when, **lab).set(frac)
