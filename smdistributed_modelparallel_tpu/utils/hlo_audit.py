"""Compiled-program X-ray (``smp.xray``): post-compile HLO audit.

Runtime observability (telemetry, flight recorder, health, roofline) says
what a run DID; this module says what the compiler BUILT. The motivating
failure is the PR-5 class: GSPMD's sharding propagation is best-effort
heuristics (GSPMD paper, arXiv 2105.04663), and one broken propagation
step silently REPLICATED the entire virtual-pipeline tick loop — every
device computing every stage, zero collective-permutes — caught only by
hand-reading HLO text. The standing guard was a raw
``hlo.count("collective-permute")`` in one test. This module makes that
inspection a first-class, structured pass over EVERY compiled step:

1. **Collective census** — every ``all-reduce`` / ``all-gather`` /
   ``reduce-scatter`` / ``collective-permute`` / ``all-to-all`` in the
   compiled module, with op counts, per-device result bytes, and
   mesh-axis attribution: ``replica_groups`` (literal or iota form) and
   ``source_target_pairs`` are matched against the device groups each
   mesh-axis subset generates, so "12 permutes on ``pp``, 4 all-reduces
   on ``rdp``" is a queryable fact, not a substring count.

2. **Sharding/replication detector** — flags (a) parameters whose
   partitioner-assigned sharding says partitioned but whose realized
   sharding is replicated, (b) gradient outputs that come back replicated
   where their parameter is partitioned, and (c) the PR-5 failure class
   itself: a pipelined program (pp > 1) whose census shows ZERO pp-axis
   collective-permutes — reported with the tick-loop ``while`` op name
   and a wasted-bytes estimate from its carry tuple.

3. **Remat census** — recomputed-FLOPs fraction: dot/convolution
   instructions that are structural duplicates (same result/operand
   shapes, contraction dims, source location) of an earlier instruction,
   FLOP-weighted. Exact for double-forward recompute (activation remat,
   the ZB split-backward's B+W forward re-runs); an upper bound when a
   transpose dot is structurally identical to its forward, or when the
   compiler left a dot without its source location (its ``stack_frame_id``;
   XLA:CPU keeps one on few dots) — same-shape dots of different layers
   then count as duplicates. Total and unique FLOPs do not depend on the
   key. Static census: multiplicities are per compiled program, not per
   loop trip.

4. **Memory breakdown** — XLA buffer assignment by class (arguments /
   outputs / temps / aliased / generated code) from ``memory_analysis``.

Every audit folds into a **program fingerprint**: a structured summary
(config snapshot, census, replication findings, remat fraction, memory,
FLOPs) plus content hashes — ``hlo_sha256`` over the metadata-stripped
HLO text and ``fingerprint`` over the canonical summary JSON. Keyed by
the step engine's compile-cache key, persisted to ``SMP_HLO_AUDIT_PATH``
(rank-qualified), published as ``smp_hlo_*`` telemetry gauges, and
referenced from the flight recorder's compile event. ``diff()`` (and
``scripts/hlo_report.py diff``) renders what changed between two
fingerprints; committed goldens gate the canonical pipeline configs in
the test tier.

``SMP_HLO_AUDIT=off`` disables the pass entirely: ``maybe_audit``
returns before touching the executable (no ``as_text`` call, no gauges —
a hard no-op, tested as such).

Import-hygiene contract: importing this module must never initialize an
accelerator backend (jax is imported for tree utilities only; devices
are touched exclusively through the mesh handed in at audit time).
"""

import hashlib
import itertools
import json
import os
import re
import time

import jax
import numpy as np

from smdistributed_modelparallel_tpu.utils.logger import get_logger
from smdistributed_modelparallel_tpu.utils.telemetry import (
    _atomic_json_dump,
    telemetry,
)

logger = get_logger()

AUDIT_ENV = "SMP_HLO_AUDIT"
AUDIT_PATH_ENV = "SMP_HLO_AUDIT_PATH"

COLLECTIVE_OPS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
)

# `-done` halves of async pairs carry no new information (the `-start`
# already holds the groups and the payload shape) and would double-count.
_COLL_RE = re.compile(
    r"=\s*(?P<shape>\([^=]*?\)|\S+)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(?P<suffix>-start|-done)?\("
)
_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_GROUPS_LITERAL_RE = re.compile(r"replica_groups=\{(\{[0-9, ]*\}(?:,\s*\{[0-9, ]*\})*)\}")
_GROUP_RE = re.compile(r"\{([0-9, ]*)\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[([0-9,]+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?"
)
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)*)\}")
_PAIR_RE = re.compile(r"\{(\d+),(\d+)\}")
_DOT_RE = re.compile(r"=\s*(?P<shape>\S+)\s+(?P<op>dot|convolution)\(")
_CONTRACT_RE = re.compile(
    r"lhs_contracting_dims=\{([0-9,]*)\}, rhs_contracting_dims=\{([0-9,]*)\}"
)
_METADATA_RE = re.compile(r"metadata=\{[^}]*\}")
# The module header's source tables (file names, function names, line and
# column of every location, stack frames), up to the first computation.
_SOURCE_TABLES_RE = re.compile(
    r"^FileNames\n.*?^StackFrames\n.*?\n\n", re.DOTALL | re.MULTILINE
)
# An instruction's source location is its stack frame in those tables.
_SOURCE_RE = re.compile(r"stack_frame_id=(\d+)")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_WHILE_RE = re.compile(r"%?([\w.\-]+)\s*=\s*(\([^=]*?\))\s+while\(")

def strip_source_metadata(hlo_text):
    """HLO text without what only says where in the source an instruction
    came from: per-instruction ``metadata={...}`` and the module header's
    file / function / line-and-column / stack-frame tables. Two programs
    that differ only by an edit above the traced function compare (and
    hash) equal after this."""
    return _METADATA_RE.sub("", _SOURCE_TABLES_RE.sub("", hlo_text))


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}


def enabled():
    """Audit gate: ``SMP_HLO_AUDIT=off``/``0`` disables (default on)."""
    return os.environ.get(AUDIT_ENV, "on").lower() not in ("off", "0", "false")


# ----------------------------------------------------------------------
# HLO text parsing
# ----------------------------------------------------------------------


def _shape_bytes(shape_str):
    """Total bytes of every array shape token in an HLO shape string
    (sums tuple elements; scalars count one element)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        width = _DTYPE_BYTES.get(dtype)
        if width is None:
            continue  # token/opaque types carry no payload bytes
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * width
    return total


def _parse_replica_groups(line):
    """The replica groups of one collective line as a list of int tuples,
    ``"all"`` for the empty ``replica_groups={}`` (every participant in
    one group), or None when the line carries none."""
    if "replica_groups={}" in line:
        return "all"
    m = _GROUPS_LITERAL_RE.search(line)
    if m:
        groups = []
        for g in _GROUP_RE.findall(m.group(1)):
            ids = tuple(int(x) for x in g.replace(" ", "").split(",") if x)
            if ids:
                groups.append(ids)
        return groups or None
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        # Iota form [g0,g1,...]<=[r0,r1,...]T(perm): arange over the
        # reshape dims, transposed, flattened, then rows of the left
        # shape's trailing dim are the groups.
        left = [int(x) for x in m.group(1).split(",")]
        reshape = [int(x) for x in m.group(2).split(",")]
        ids = np.arange(int(np.prod(reshape))).reshape(reshape)
        if m.group(3):
            ids = ids.transpose([int(x) for x in m.group(3).split(",")])
        ids = ids.ravel().reshape(-1, left[-1])
        return [tuple(int(x) for x in row) for row in ids]
    return None


def _parse_pairs(line):
    m = _PAIRS_RE.search(line)
    if not m:
        return None
    return [(int(a), int(b)) for a, b in _PAIR_RE.findall(m.group(1))]


def _mesh_coord_maps(mesh):
    """Participant-id -> per-axis coordinate maps: ``pos`` keys by the
    mesh's flattened device order (the SPMD partition numbering), ``id``
    by device id (``use_global_device_ids=true`` groups)."""
    if mesh is None:
        return None
    by_pos, by_id = {}, {}
    axes = tuple(mesh.axis_names)
    flat = list(np.asarray(mesh.devices).ravel())
    shape = np.asarray(mesh.devices).shape
    for pos, coords in enumerate(np.ndindex(*shape)):
        by_pos[pos] = coords
        dev = flat[pos]
        did = getattr(dev, "id", pos)
        by_id[did] = coords
    return {"axes": axes, "pos": by_pos, "id": by_id}


def _axis_subsets(mesh):
    """Nontrivial mesh-axis subsets, smallest first, each with the
    partition of participant coordinates it generates."""
    axes = [
        (i, a) for i, a in enumerate(mesh.axis_names)
        if dict(mesh.shape).get(a, 1) > 1
    ]
    out = []
    for size in range(1, len(axes) + 1):
        for combo in itertools.combinations(axes, size):
            out.append(combo)
    return out


def _attribute_groups(groups, mesh, maps, use_global_ids):
    """Mesh-axis label for a replica-group set: the smallest axis subset
    whose generated device partition matches exactly. ``"world"`` when the
    match is every nontrivial axis, ``"self"`` for singleton groups,
    ``"unattributed"`` when nothing matches (manual groups, sliced
    meshes)."""
    if maps is None:
        return "unattributed"
    if groups and all(len(g) == 1 for g in groups):
        return "self"
    coord_of = maps["id"] if use_global_ids else maps["pos"]
    try:
        got = {frozenset(g) for g in groups}
    except TypeError:
        return "unattributed"
    if not all(i in coord_of for g in groups for i in g):
        return "unattributed"
    subsets = _axis_subsets(mesh)
    n_nontrivial = max((len(s) for s in subsets), default=0)
    for combo in subsets:
        vary = {i for i, _ in combo}
        buckets = {}
        for pid, coords in coord_of.items():
            key = tuple(c for i, c in enumerate(coords) if i not in vary)
            buckets.setdefault(key, set()).add(pid)
        if {frozenset(b) for b in buckets.values()} == got:
            if len(combo) == n_nontrivial and len(combo) > 1:
                return "world"
            return "+".join(a for _, a in combo)
    return "unattributed"


def _attribute_pairs(pairs, maps, use_global_ids):
    """Axis label for collective-permute source/target pairs: every pair
    must step along the SAME single mesh axis."""
    if maps is None or not pairs:
        return "unattributed"
    coord_of = maps["id"] if use_global_ids else maps["pos"]
    axes = maps["axes"]
    axis_hit = None
    for src, dst in pairs:
        cs, cd = coord_of.get(src), coord_of.get(dst)
        if cs is None or cd is None:
            return "unattributed"
        diff = [i for i, (a, b) in enumerate(zip(cs, cd)) if a != b]
        if len(diff) != 1:
            return "unattributed"
        if axis_hit is None:
            axis_hit = diff[0]
        elif axis_hit != diff[0]:
            return "unattributed"
    return axes[axis_hit] if axis_hit is not None else "unattributed"


# ----------------------------------------------------------------------
# Op index: one record per instruction, from the one walk over the text
# ----------------------------------------------------------------------

#: The phases an instruction can belong to (``phase_of``), in the order
#: the rules are tried.
PHASES = ("optimizer", "recompute", "backward", "forward", "other")

_INSTR_NAME_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_SCOPE_RE = re.compile(r"smp/[\w\-]+/[\w\-]+")
# XLA's own rematerialization pass clones an instruction under its name
# plus ``.remat`` / ``.remat2`` (/ ``.remat.3`` for the tuple elements).
_XLA_REMAT_RE = re.compile(r"\.remat\d*(?:\.\d+)?$")
_BWD_TICKS = ("smp/pipeline/tick_bwd", "smp/pipeline/cooldown_weight")
_BWD_SCOPES = _BWD_TICKS + ("smp/step/accumulate",)
_FWD_SCOPES = ("smp/pipeline/embed", "smp/pipeline/tick_fwd",
               "smp/pipeline/head", "smp/step/cast_params")
# The ``-start`` a ``-done`` waits for (operand types hold no ``%``).
_DONE_OPERAND_RE = re.compile(r"-done\([^%]*%([\w.\-]+)")


def phase_of(op_name, instr_name=""):
    """Which part of the training step an instruction belongs to, from the
    markers JAX and this library write into its ``op_name`` (first match
    wins; of a ``;``-joined op_name the first part decides):

    - ``optimizer``: under the ``smp/optimizer/update`` scope;
    - ``recompute``: ``rematted_computation`` (``jax.checkpoint``), an
      instruction XLA's rematerialization cloned (``.remat`` in its
      name), or the forward half (``jvp(`` without ``transpose(``) inside
      a pipeline backward tick, which runs the stage's forward again;
    - ``backward``: ``transpose(``, a ``smp/pipeline/tick_bwd*`` /
      ``cooldown_weight`` scope, or ``smp/step/accumulate`` (microbatch
      gradients added into the accumulator);
    - ``forward``: ``jvp(``, ``smp/pipeline/{embed,tick_fwd,head}``, or
      ``smp/step/cast_params`` (the half-precision copy the forward reads);
    - ``other``: none of these (no metadata, schedule glue, RNG).
    """
    first = op_name.split(";", 1)[0]
    if "smp/optimizer/update" in first:
        return "optimizer"
    transposed = "transpose(" in first
    in_bwd_tick = any(s in first for s in _BWD_TICKS)
    if ("rematted_computation" in first
            or _XLA_REMAT_RE.search(instr_name)
            or (in_bwd_tick and "jvp(" in first and not transposed)):
        return "recompute"
    if transposed or any(s in first for s in _BWD_SCOPES):
        return "backward"
    if "jvp(" in first or any(s in first for s in _FWD_SCOPES):
        return "forward"
    return "other"


def scopes_of(op_name):
    """Every ``smp/<subsystem>/<name>`` segment of an ``op_name``, outermost
    first (a layer's scope, then its attention's or its expert layer's)."""
    return tuple(_SCOPE_RE.findall(op_name.split(";", 1)[0]))


def scope_of(op_name):
    """The ``smp/<subsystem>/<name>`` segment nearest the leaf, or None."""
    found = scopes_of(op_name)
    return found[-1] if found else None


#: The scope round the user's step function (``step.py``): outermost, and
#: alone on what the step function itself wrote.
USER_SCOPE = "smp/step/user"

# A kernel the TPU compiler makes of an instruction by itself (each
# ``lax.ragged_dot`` and the group metadata it reads): a Mosaic custom-call
# whose ``op_name`` is the compiler's own word for it, with no path of the
# program in it (``metadata={op_name="ragged-dot-none"}``).
_KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
# Instructions that only move what they are given: a nameless kernel's
# neighbours are looked for through them.
_PASS_THROUGH_RE = re.compile(
    r"^(?:\([^()]*\)|\S+)\s+(?:get-tuple-element|bitcast|copy|tuple|"
    r"reshape|transpose|opt-barrier|copy-start|copy-done|slice-start|"
    r"slice-done)\(")
# Instructions that take no time of their own: nobody asks what they are
# near.
_FREE_RE = re.compile(
    r"^(?:\([^()]*\)|\S+)\s+(?:parameter|constant|get-tuple-element|"
    r"tuple|bitcast)\(")
_HOPS = 4


def _compiler_kernel(line, op_name):
    """The ``kernel`` mark of an instruction the compiler turned into a
    kernel of its own, from what it keeps on the line; ``None`` for every
    other instruction (a Pallas kernel's ``op_name`` is a path)."""
    if _KERNEL_TARGET not in line or "/" in op_name:
        return None
    if "ragged_dot_tiling=" in line:      # its frontend attribute
        return "ragged_dot"
    return op_name.replace("-", "_") or "unnamed"


_PALLAS_NAME_RE = re.compile(r"smp_[a-z0-9_]+")


def _pallas_kernel(op_name):
    """The name a Pallas kernel of this library was given (``pallas_call``'s
    ``name``, the last segment of its kind in the ``op_name``'s path)."""
    names = _PALLAS_NAME_RE.findall(op_name.split(";", 1)[0])
    return names[-1] if names else "unnamed"


def _common_scopes(names, records, through, past_kernels, seen=None,
                   hops=_HOPS):
    """The scope path common to the marked instructions among ``names``
    (the longest common prefix of their ``scopes``), looking through an
    unmarked instruction that only moves data, and through a kernel of
    the compiler's that has no scopes (or, with ``past_kernels``, any: what
    it inherited is not its neighbour's own), to that one's neighbours in
    the same direction (``through``: name -> names), ``hops`` deep."""
    seen = set() if seen is None else seen
    found = []
    for name in names:
        if name in seen:
            continue
        seen.add(name)
        rec = records.get(name)
        if rec is None:
            continue
        scopes = rec.get("scopes") or (
            (rec["scope"],) if rec.get("scope") else ())
        if hops and ("kernel" in rec and (past_kernels or not scopes)
                     or not scopes and rec.get("_moves")):
            scopes = _common_scopes(through.get(name, ()), records, through,
                                    past_kernels, seen, hops - 1)
        if scopes:
            found.append(tuple(scopes))
    if not found:
        return ()
    common = found[0]
    for other in found[1:]:
        n = 0
        while n < min(len(common), len(other)) and common[n] == other[n]:
            n += 1
        common = common[:n]
    return common


def _neighbours_scopes(name, records, operands, users):
    """The scopes common to an instruction's marked operands, else (none,
    or the user's function alone) to its marked users."""
    scopes = _common_scopes(
        operands.get(name, ()), records, operands, past_kernels=True)
    if not scopes or scopes[-1] == USER_SCOPE:
        after = _common_scopes(
            users.get(name, ()), records, users, past_kernels=False)
        if len(after) > len(scopes):
            scopes = after
    return scopes


def _inherit_kernel_scopes(records, kernels, operands, users):
    """Give each compiler-made kernel (``kernels``: names, in text order)
    the scopes of the code that made it: those common to its marked
    operands, else (none, or the user's function alone) to its marked
    users. Operands go first: a grouped product's rows are made ready by
    the scope that calls it (the masked gather, the gated activation),
    while its result may leave that scope at once (the second product
    feeds ``smp/moe/combine``'s kernel); the products whose rows come
    straight from a buffer the compiler allocated are found from their
    users. Later kernels first, and among users a kernel counts with what
    it inherited: the group metadata, whose only operand is a parameter,
    takes the scopes of the products it feeds."""
    for name in reversed(kernels):
        scopes = _neighbours_scopes(name, records, operands, users)
        if not scopes:
            continue
        rec = records[name]
        rec["scope"] = scopes[-1]
        rec["inherited"] = True
        if len(scopes) > 1:
            rec["scopes"] = scopes


def _mark_what_the_unscoped_are_near(records, loose, operands, users):
    """``near`` on each instruction the compiler made with no name of the
    program's at all (``loose``: a layout copy, an asynchronous copy's
    halves, a relayout fused by the compiler): the scopes of its
    neighbours by the kernels' rule. It stays unscoped (``scope`` is
    ``None``: no reader of scopes counts it); ``seconds_by_scope`` says
    beside which scopes the unscoped seconds lie."""
    for name in loose:
        near = _neighbours_scopes(name, records, operands, users)
        if near:
            records[name]["near"] = near


def _collective_axis(op, line, mesh, maps):
    use_global = "use_global_device_ids=true" in line
    if op == "collective-permute":
        return _attribute_pairs(_parse_pairs(line), maps, use_global)
    groups = _parse_replica_groups(line)
    if groups is None:
        return "unattributed"
    if groups == "all":
        return "world"
    return _attribute_groups(groups, mesh, maps, use_global)


def op_records(hlo_text, mesh=None):
    """``{instruction name: record}`` over every instruction of the HLO
    text, in text order, keyed as a device trace prints the name (no
    ``%``). A record holds ``phase`` (``phase_of``) and ``scope``
    (``scope_of``), and where scopes are nested ``scopes`` (``scopes_of``:
    every scope round the instruction); a collective's also ``op``, ``axis`` (the mesh-axis
    label of its ``replica_groups`` / ``source_target_pairs``) and
    ``bytes`` (per-device result payload). The ``-done`` half of an async
    pair takes its axis from its ``-start`` and is marked ``done`` (the
    census counts the pair once, a trace times both halves).

    An instruction the compiler left without ``op_name`` takes that of
    the computation it calls (a fusion: its root's, else the first one
    inside), so a fusion is never anonymous where its body is not; and
    one whose ``op_name`` holds no marker takes the phase of its first
    operand that has one. Both hold for an executable that a compile
    cache filled by an older build hands back, whose metadata is that
    build's and lacks any scope added since.

    A kernel the compiler made of one instruction by itself (each
    ``lax.ragged_dot``; ``_compiler_kernel``) keeps no path of the program
    in its ``op_name``. Its record is marked ``kernel`` (``"ragged_dot"``)
    and takes the scopes of the code that made it from its neighbours
    (``_inherit_kernel_scopes``: ``scope`` and ``scopes`` as if they were
    its own, and ``inherited: True``); with no marked neighbour it stays
    without a scope. Every other instruction that has no scope and can
    take time keeps ``scope: None`` and is told what it is ``near`` (the
    same rule's answer, for ``seconds_by_scope``'s account of the
    unscoped seconds). A Pallas kernel's ``op_name`` is a path like any
    other instruction's; its record says which kernel it is, ``pallas``
    (``"smp_flash_fwd"``), for ``kernel_census``."""
    records = {}
    maps = _mesh_coord_maps(mesh)
    comp = None
    comp_op_name = {}    # computation -> its root's op_name, else the first
    kernels, loose, operands, users = [], [], {}, {}
    for lineno, line in enumerate(hlo_text.splitlines()):
        header = _COMP_HEADER_RE.match(line)
        if header is not None:
            comp = header.group(1)
            continue
        named = _INSTR_NAME_RE.match(line)
        coll = _COLL_RE.search(line)
        if named is None and coll is None:
            continue
        name = named.group(1) if named else f"#{lineno}"
        if name in records:      # hand-written text; XLA's names are unique
            name = f"{name}#{lineno}"
        found = _OP_NAME_RE.search(line)
        op_name = found.group(1) if found else ""
        if op_name:
            if "ROOT " in line[:line.find("=")]:
                comp_op_name[comp] = op_name
            else:
                comp_op_name.setdefault(comp, op_name)
        else:
            called = _CALLS_RE.search(line)
            if called is not None:
                op_name = comp_op_name.get(called.group(1), "")
        scopes = scopes_of(op_name)
        rec = {"phase": phase_of(op_name, name),
               "scope": scopes[-1] if scopes else None}
        if len(scopes) > 1:
            rec["scopes"] = scopes
        refs = _REF_RE.findall(line[named.end():]) if named else ()
        if rec["phase"] == "other":
            # No marker of its own (a compiler-made copy, the add that
            # accumulates gradients): it works on what its first marked
            # operand produced. Operands come first in the text, so this
            # follows a chain (copy of get-tuple-element of a while).
            for ref in refs:
                phase = records.get(ref, rec)["phase"]
                if phase != "other":
                    rec["phase"] = phase
                    break
        kernel = _compiler_kernel(line, op_name)
        if kernel is not None:
            rec["kernel"] = kernel
            kernels.append(name)
        elif _KERNEL_TARGET in line:
            rec["pallas"] = _pallas_kernel(op_name)
        if named is not None:
            # What a nameless instruction's neighbours are found from: who
            # reads whom.
            operands[name] = refs
            for ref in refs:
                users.setdefault(ref, []).append(name)
            rhs = line[named.end():]
            if not scopes and kernel is None and not _FREE_RE.match(rhs):
                loose.append(name)
            if not scopes and _PASS_THROUGH_RE.match(rhs):
                rec["_moves"] = True
        if coll is not None:
            op = coll.group("op")
            if coll.group("suffix") == "-done":
                start = _DONE_OPERAND_RE.search(line)
                start = records.get(start.group(1), {}) if start else {}
                rec.update(op=op, bytes=0, done=True,
                           axis=start.get("axis", "unattributed"))
            else:
                rec.update(op=op, bytes=_shape_bytes(coll.group("shape")),
                           axis=_collective_axis(op, line, mesh, maps))
        records[name] = rec
    _inherit_kernel_scopes(records, kernels, operands, users)
    _mark_what_the_unscoped_are_near(records, loose, operands, users)
    for rec in records.values():
        rec.pop("_moves", None)
    return records


def census_of(records):
    """The collective census as a sum over ``op_records``."""
    census = {}
    for rec in records.values():
        if "op" not in rec or rec.get("done"):
            continue
        ent = census.setdefault(
            rec["op"], {"count": 0, "bytes": 0, "axes": {}})
        ent["count"] += 1
        ent["bytes"] += rec["bytes"]
        ax = ent["axes"].setdefault(rec["axis"], {"count": 0, "bytes": 0})
        ax["count"] += 1
        ax["bytes"] += rec["bytes"]
    return census


#: The phases every kernel of ``kernel_census`` is counted under, found
#: or not: a kernel that stopped being recomputed reads 0, not nothing.
_KERNEL_PHASES = ("forward", "recompute", "backward")


def kernel_census(records):
    """``{kernel: {phase: count}}`` over the Mosaic kernels of
    ``op_records``, the program's own (``pallas``) and the compiler's
    (``kernel``): instructions of the compiled program, each once whatever
    its loop's trip count. Every kernel holds ``forward``, ``recompute``
    and ``backward``, so ``smp_flash_fwd`` under ``recompute`` reads 0
    where a checkpointed layer kept the kernel's outputs
    (``parallel/memory.remat_policy``) and as many as under ``forward``
    where its backward pass runs it again."""
    census = {}
    for rec in records.values():
        kernel = rec.get("pallas") or rec.get("kernel")
        if kernel is None:
            continue
        phases = census.setdefault(kernel, dict.fromkeys(_KERNEL_PHASES, 0))
        phases[rec["phase"]] = phases.get(rec["phase"], 0) + 1
    return census


def collective_census(hlo_text, mesh=None):
    """``{op: {"count", "bytes", "axes": {label: {"count", "bytes"}}}}``
    over every collective instruction in the HLO text. ``bytes`` is the
    per-device result payload (summed over tuple elements)."""
    return census_of(op_records(hlo_text, mesh))


def remat_census(hlo_text):
    """``{"flops", "recomputed_flops", "fraction", "dots",
    "recomputed_dots"}`` — FLOP-weighted structural-duplicate census of
    dot/convolution instructions (see module docstring for exactness)."""
    seen = {}
    for line in hlo_text.splitlines():
        m = _DOT_RE.search(line)
        if m is None:
            continue
        shapes = _SHAPE_RE.findall(line)
        contract = _CONTRACT_RE.search(line)
        src = _SOURCE_RE.search(line)
        key = (
            m.group("op"),
            tuple(shapes[:3]),
            contract.groups() if contract else None,
            src.groups() if src else None,
        )
        flops = _dot_flops(m.group("op"), shapes, contract)
        seen.setdefault(key, []).append(flops)
    total_f = recomputed_f = 0.0
    total_n = recomputed_n = 0
    for flops_list in seen.values():
        total_n += len(flops_list)
        total_f += sum(flops_list)
        if len(flops_list) > 1:
            recomputed_n += len(flops_list) - 1
            recomputed_f += sum(flops_list) - flops_list[0]
    fraction = recomputed_f / total_f if total_f else 0.0
    return {
        "flops": total_f,
        "recomputed_flops": recomputed_f,
        "fraction": round(fraction, 4),
        "dots": total_n,
        "recomputed_dots": recomputed_n,
    }


def _dot_flops(op, shapes, contract):
    """2 * |result| * |contraction| for a dot (from its text shapes);
    convolutions fall back to 2 * |result| (kernel size unparsed)."""
    def _dims(shape):
        _, dims = shape
        return [int(d) for d in dims.split(",") if d]

    if not shapes:
        return 0.0
    result = float(np.prod(_dims(shapes[0]))) if _dims(shapes[0]) else 1.0
    if op == "dot" and contract is not None and len(shapes) >= 2:
        lhs = _dims(shapes[1])
        k = 1.0
        for i in contract.group(1).split(","):
            if i and int(i) < len(lhs):
                k *= lhs[int(i)]
        return 2.0 * result * k
    return 2.0 * result


def while_carries(hlo_text):
    """``[{"name", "op_name", "bytes"}]`` for every ``while`` instruction
    (carry-tuple bytes from its result shape), largest first."""
    out = []
    for line in hlo_text.splitlines():
        m = _WHILE_RE.search(line)
        if m is None:
            continue
        op_name = _OP_NAME_RE.search(line)
        out.append({
            "name": m.group(1),
            "op_name": op_name.group(1) if op_name else m.group(1),
            "bytes": _shape_bytes(m.group(2)),
        })
    out.sort(key=lambda w: -w["bytes"])
    return out


# ----------------------------------------------------------------------
# ZeRO-3 traffic report (sharded_params: zero3)
# ----------------------------------------------------------------------

_COMP_HEADER_RE = re.compile(
    r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\([^)]*\))?\s*\(.*\)\s*->.*\{\s*$"
)
_INSTR_RE = re.compile(r"^\s*(ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_REF_RE = re.compile(r"%([\w.\-]+)")


def _computations(hlo_text):
    """``(name, [instruction lines])`` per computation in the HLO text."""
    name, lines = None, []
    for line in hlo_text.splitlines():
        m = _COMP_HEADER_RE.match(line)
        if m is not None:
            if name is not None:
                yield name, lines
            name, lines = m.group(1), []
            continue
        if line.startswith("}"):
            if name is not None:
                yield name, lines
            name, lines = None, []
            continue
        if name is not None:
            lines.append(line)
    if name is not None:
        yield name, lines


# The result-type prefix of a tuple-typed instruction can contain
# ``/*index=N*/`` comments, so the paren alternative must key on paren
# nesting (HLO types never nest parens), not on '='-freedom.
_RHS_OP_RE = re.compile(r"^(?:\([^()]*\)|\S+)\s+([a-z][a-z0-9\-]*)\(")
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")

#: Pure data-movement ops: an all-gather whose transitive users are ONLY
#: these (ending at the body ROOT tuple) computes nothing this iteration —
#: it is parked in the loop carry for the next tick. Anything else
#: (dot, a fusion whose body computes, convert feeding compute, ...)
#: counts as compute, so gathers consumed at use never misclassify as
#: registers. ``parameter``/``constant`` matter only for classifying
#: fused computations as move-only.
_MOVE_OPS = frozenset((
    "tuple", "copy", "bitcast", "get-tuple-element", "opt-barrier",
    "all-gather-done", "transpose", "reshape", "parameter", "constant",
))


def zero3_prefetch_evidence(hlo_text):
    """Structural double-buffering check: inside some while-loop body that
    performs both an all-gather and matmuls, at least one all-gather's
    result never feeds this iteration's compute — its only transitive
    users are data-movement ops (including fusions of them, e.g. the
    copy/bitcast fusions XLA builds for carry writes) ending at the carry
    tuple: the transfer register, i.e. the next layer's gather is issued
    before this layer's dependent matmuls. Returns the count of such
    register gathers."""
    comps = list(_computations(hlo_text))
    # A fusion is data-movement iff every instruction of its called
    # computation is.
    move_only = {}
    for name, lines in comps:
        ok = True
        for line in lines:
            m = _INSTR_RE.match(line)
            if m is None:
                continue
            km = _RHS_OP_RE.match(m.group(3))
            if km is None or km.group(1) not in _MOVE_OPS:
                ok = False
                break
        move_only[name] = ok

    registers = 0
    for name, lines in comps:
        users, kinds, dots, gathers, calls = {}, {}, set(), [], {}
        for line in lines:
            m = _INSTR_RE.match(line)
            if m is None:
                continue
            iname, rhs = m.group(2), m.group(3)
            for op in _REF_RE.findall(rhs):
                if op != iname:
                    users.setdefault(op, set()).add(iname)
            km = _RHS_OP_RE.match(rhs)
            kinds[iname] = km.group(1) if km else "?"
            if kinds[iname] == "fusion":
                fm = _CALLS_RE.search(rhs)
                if fm:
                    calls[iname] = fm.group(1)
            cm = _COLL_RE.search(line)
            if cm is not None and cm.group("op") == "all-gather" and (
                    cm.group("suffix") != "-done"):
                gathers.append(iname)
            if _DOT_RE.search(line):
                dots.add(iname)
        if not gathers or not dots:
            continue

        def moves(iname):
            kind = kinds.get(iname)
            if kind == "fusion":
                return move_only.get(calls.get(iname, ""), False)
            return kind in _MOVE_OPS

        for g in gathers:
            seen, frontier = set(), list(users.get(g, ()))
            parked = True
            while frontier:
                cur = frontier.pop()
                if cur in seen:
                    continue
                seen.add(cur)
                if not moves(cur):
                    parked = False
                    break
                frontier.extend(users.get(cur, ()))
            if parked and seen:
                registers += 1
    return registers


def tp_ring_evidence(hlo_text, mesh=None):
    """Structural double-buffering check for the tp_overlap rings: inside
    some while-loop body that performs both a collective-permute and
    matmuls, at least one permute's result never feeds this iteration's
    compute — its only transitive users are data-movement ops ending at
    the carry tuple. That is the parked ring hop: the block in transit is
    consumed only by the NEXT iteration's partial matmul, so the hop
    rides under the matmul on the block already in hand. Returns the
    count of such parked hops (the permute-flavored sibling of
    ``zero3_prefetch_evidence``). With ``mesh``, only TP-ATTRIBUTED
    permutes count — a parked pipeline-stage or cp-ring hop must not
    stand in for the tp ring's own double buffering."""
    from smdistributed_modelparallel_tpu.backend.topology import TP_AXIS

    maps = _mesh_coord_maps(mesh)
    comps = list(_computations(hlo_text))
    move_only = {}
    for name, lines in comps:
        ok = True
        for line in lines:
            m = _INSTR_RE.match(line)
            if m is None:
                continue
            km = _RHS_OP_RE.match(m.group(3))
            if km is None or km.group(1) not in _MOVE_OPS:
                ok = False
                break
        move_only[name] = ok

    parked = 0
    for name, lines in comps:
        users, kinds, dots, hops, calls = {}, {}, set(), [], {}
        for line in lines:
            m = _INSTR_RE.match(line)
            if m is None:
                continue
            iname, rhs = m.group(2), m.group(3)
            for op in _REF_RE.findall(rhs):
                if op != iname:
                    users.setdefault(op, set()).add(iname)
            km = _RHS_OP_RE.match(rhs)
            kinds[iname] = km.group(1) if km else "?"
            if kinds[iname] == "fusion":
                fm = _CALLS_RE.search(rhs)
                if fm:
                    calls[iname] = fm.group(1)
            cm = _COLL_RE.search(line)
            if cm is not None and cm.group("op") == "collective-permute" \
                    and cm.group("suffix") != "-done":
                if maps is None or _attribute_pairs(
                    _parse_pairs(line), maps,
                    "use_global_device_ids=true" in line,
                ) == TP_AXIS:
                    hops.append(iname)
            if _DOT_RE.search(line):
                dots.add(iname)
        if not hops or not dots:
            continue

        def moves(iname):
            kind = kinds.get(iname)
            if kind == "fusion":
                return move_only.get(calls.get(iname, ""), False)
            if kind == "collective-permute-done":
                return True
            return kind in _MOVE_OPS

        for h in hops:
            seen, frontier = set(), list(users.get(h, ()))
            ok = True
            while frontier:
                cur = frontier.pop()
                if cur in seen:
                    continue
                seen.add(cur)
                if not moves(cur):
                    ok = False
                    break
                frontier.extend(users.get(cur, ()))
            if ok and seen:
                parked += 1
    return parked


#: op_name path markers of the per-layer block family (the overlapped
#: path): the nn transformer's scanned stack and the zoo stack. A tp
#: all-gather whose op_name carries one of these belongs to a block
#: matmul the ring was supposed to decompose; collectives at the
#: embed/head/optimizer boundary (tied LM-head dot, token-id gathers,
#: param-update resharding GSPMD chooses on its own) are reported
#: separately and allowed.
_LAYER_PATH_MARKERS = ("seq_layers/", "/layers/", "layers/block")


def tp_overlap_report(hlo_text, mesh=None):
    """Overlapped-tensor-parallelism report over the compiled program
    (``tp_overlap: ring``): the decomposed-ppermute census attributed to
    the tp axis, the parked-hop double-buffering evidence, and the
    residual synchronous tp collectives the ring is supposed to have
    eliminated. ``overlap_evidence`` is the gate the golden commits to:
    parked hops present AND zero residual tp all-gathers on the
    overlapped path (the per-layer block family — boundary collectives
    at embed/head/optimizer are reported as ``tp_boundary_*``). Bytes
    are per-device result payloads, the census convention."""
    from smdistributed_modelparallel_tpu.backend.topology import TP_AXIS

    maps = _mesh_coord_maps(mesh)
    report = {
        "ring_permute_ops": 0, "ring_permute_bytes": 0,
        "tp_allgather_ops": 0, "tp_allgather_bytes": 0,
        "tp_boundary_allgather_ops": 0, "tp_boundary_allgather_bytes": 0,
        "tp_reduce_scatter_ops": 0, "tp_allreduce_ops": 0,
    }
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None or m.group("suffix") == "-done":
            continue
        op = m.group("op")
        use_global = "use_global_device_ids=true" in line
        if op == "collective-permute":
            axis = _attribute_pairs(_parse_pairs(line), maps, use_global)
        else:
            groups = _parse_replica_groups(line)
            if groups is None:
                axis = "unattributed"
            elif groups == "all":
                axis = "world"
            else:
                axis = _attribute_groups(groups, mesh, maps, use_global)
        if axis != TP_AXIS:
            continue
        nbytes = _shape_bytes(m.group("shape"))
        if op == "collective-permute":
            report["ring_permute_ops"] += 1
            report["ring_permute_bytes"] += nbytes
        elif op == "all-gather":
            onm = _OP_NAME_RE.search(line)
            in_layer = bool(onm) and any(
                marker in onm.group(1) for marker in _LAYER_PATH_MARKERS
            )
            key = "tp_allgather" if in_layer else "tp_boundary_allgather"
            report[f"{key}_ops"] += 1
            report[f"{key}_bytes"] += nbytes
        elif op == "reduce-scatter":
            report["tp_reduce_scatter_ops"] += 1
        elif op == "all-reduce":
            report["tp_allreduce_ops"] += 1
    report["parked_hops"] = tp_ring_evidence(hlo_text, mesh=mesh)
    # Known limitation: tp all-REDUCES cannot enter this gate — a clean
    # ring program legitimately carries them (replicated-param grads:
    # layernorms, biases, the embed/head boundary), and HLO offers no
    # robust marker separating those from a row-parallel matmul that
    # fell back to its synchronous all-reduce. Indivisible-geometry
    # fallbacks are therefore surfaced by the collective_matmul
    # warn-once logs and the census's tp_allreduce_ops count (pinned by
    # the golden), not by this boolean.
    report["overlap_evidence"] = bool(
        report["ring_permute_ops"] > 0
        and report["parked_hops"] > 0
        and report["tp_allgather_ops"] == 0
        and report["tp_reduce_scatter_ops"] == 0
    )
    return report


def _tp_overlap_mode(cfg):
    """The CANONICAL tp_overlap mode (collective_matmul.tp_overlap_mode):
    "off" whenever the knob cannot shape the program (tp=1, cp>1 — the
    documented, warned fallbacks). The audit gates on this, like the
    step-cache key and exec-cache knob facts, so an intentionally
    disabled ring never triggers the missing_tp_ring class."""
    from smdistributed_modelparallel_tpu.ops.collective_matmul import (
        tp_overlap_mode,
    )

    return tp_overlap_mode(cfg) if cfg is not None else "off"


def _tp_overlap_findings(tp_block, cfg, mesh):
    """The neutered-ring class: a program built under ``tp_overlap:
    ring`` whose census shows ZERO tp-axis collective-permutes — the
    ring decomposition silently did not lower (a neutered constraint, a
    fallen-back call site) and the layers are back on synchronous GSPMD
    collectives. Residual LAYER-PATH tp all-gathers alongside a
    requested ring are a second finding (the overlap claim does not
    hold for those bytes); boundary collectives (embed/head/optimizer)
    are reported in the ``tp_overlap`` block but never flagged."""
    from smdistributed_modelparallel_tpu.backend.topology import TP_AXIS

    findings = []
    if tp_block is None:
        return findings
    mode = _tp_overlap_mode(cfg)
    tp = int(getattr(cfg, "tensor_parallel_degree", 1) or 1) if cfg else 1
    mesh_tp = dict(mesh.shape).get(TP_AXIS, 1) if mesh is not None else 1
    if mode != "ring" or tp <= 1 or mesh_tp <= 1:
        return findings
    ag_ops = tp_block.get("tp_allgather_ops", 0)
    ag_bytes = tp_block.get("tp_allgather_bytes", 0)
    if tp_block.get("ring_permute_ops", 0) == 0:
        findings.append({
            "kind": "missing_tp_ring",
            "tensor": "(tp matmul family)",
            "bytes": ag_bytes,
            "bytes_wasted": 0,
            "detail": (
                "tp_overlap=ring but the compiled program has 0 tp-axis "
                "collective-permutes: the ring decomposition did not "
                "lower and the tp matmuls are back on synchronous GSPMD "
                "collectives"
            ),
        })
    if ag_ops > 0:
        findings.append({
            "kind": "tp_residual_allgather",
            "tensor": "(tp layer blocks)",
            "bytes": ag_bytes,
            "bytes_wasted": 0,
            "detail": (
                f"tp_overlap=ring but {ag_ops} tp-axis all-gather(s) "
                "remain on the layer-block path "
                f"({ag_bytes} bytes/device stay synchronous on the "
                "critical path)"
            ),
        })
    return findings


def zero_report(hlo_text, mesh=None):
    """ZeRO-3 collective-traffic report over the compiled program: rdp-axis
    parameter-gather and gradient-scatter volume, how much of it is issued
    inside loop bodies (where it can overlap the loop's compute — the
    epilogue position on the critical tail cannot), and the structural
    double-buffering evidence from ``zero3_prefetch_evidence``. Bytes are
    per-device result payloads, same convention as the census."""
    from smdistributed_modelparallel_tpu.backend.topology import RDP_AXIS

    maps = _mesh_coord_maps(mesh)
    totals = {
        "gather_ops": 0, "gather_bytes": 0,
        "scatter_ops": 0, "scatter_bytes": 0,
        "allreduce_ops": 0, "allreduce_bytes": 0,
    }
    interior_bytes = total_gs_bytes = 0
    loop_gathers = 0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None or m.group("suffix") == "-done":
            continue
        op = m.group("op")
        if op not in ("all-gather", "reduce-scatter", "all-reduce"):
            continue
        groups = _parse_replica_groups(line)
        use_global = "use_global_device_ids=true" in line
        if groups is None:
            axis = "unattributed"
        elif groups == "all":
            axis = "world"
        else:
            axis = _attribute_groups(groups, mesh, maps, use_global)
        if axis != RDP_AXIS:
            continue
        nbytes = _shape_bytes(m.group("shape"))
        onm = _OP_NAME_RE.search(line)
        in_loop = bool(onm and "while" in onm.group(1))
        if op == "all-gather":
            totals["gather_ops"] += 1
            totals["gather_bytes"] += nbytes
            loop_gathers += int(in_loop)
        elif op == "reduce-scatter":
            totals["scatter_ops"] += 1
            totals["scatter_bytes"] += nbytes
        else:
            totals["allreduce_ops"] += 1
            totals["allreduce_bytes"] += nbytes
            continue  # all-reduce volume is reported but not "overlap"
        total_gs_bytes += nbytes
        if in_loop:
            interior_bytes += nbytes
    totals["loop_gather_ops"] = loop_gathers
    totals["overlap_fraction"] = round(
        interior_bytes / total_gs_bytes, 4
    ) if total_gs_bytes else 0.0
    totals["prefetch_registers"] = zero3_prefetch_evidence(hlo_text)
    return totals


def memory_breakdown(compiled):
    """XLA buffer-assignment byte classes of a compiled executable, or
    ``{}`` when the backend won't say."""
    out = {}
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return out
    if ma is None:
        return out
    for attr, key in (
        ("argument_size_in_bytes", "argument_bytes"),
        ("output_size_in_bytes", "output_bytes"),
        ("temp_size_in_bytes", "temp_bytes"),
        ("alias_size_in_bytes", "alias_bytes"),
        ("generated_code_size_in_bytes", "generated_code_bytes"),
    ):
        v = getattr(ma, attr, None)
        if v is not None:
            out[key] = int(v)
    if {"argument_bytes", "output_bytes", "temp_bytes"} <= out.keys():
        out["total_bytes"] = (
            out["argument_bytes"] + out["output_bytes"] + out["temp_bytes"]
        )
    return out


# ----------------------------------------------------------------------
# Sharding / replication detector
# ----------------------------------------------------------------------


def _spec_partitions(sharding, mesh):
    """How many ways a NamedSharding's spec splits the value (1 ==
    effectively replicated intent)."""
    spec = getattr(sharding, "spec", None)
    if spec is None or mesh is None:
        return 1
    n = 1
    sizes = dict(mesh.shape)
    for entry in spec:
        if entry is None:
            continue
        for axis in entry if isinstance(entry, tuple) else (entry,):
            if isinstance(axis, str):
                n *= sizes.get(axis, 1)
    return n


def _leaf_path(path):
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def _param_findings(params, expected_shardings, mesh, min_bytes):
    """Partitioner said partitioned, realized array is replicated."""
    findings = []
    if params is None or expected_shardings is None:
        return findings
    try:
        exp_leaves = jax.tree_util.tree_leaves(expected_shardings)
        par = jax.tree_util.tree_flatten_with_path(params)[0]
    except Exception:
        return findings
    if len(exp_leaves) != len(par):
        return findings
    for (path, leaf), want in zip(par, exp_leaves):
        nparts = _spec_partitions(want, mesh)
        if nparts <= 1:
            continue
        realized = getattr(leaf, "sharding", None)
        nbytes = int(getattr(leaf, "nbytes", 0) or 0)
        if realized is None or nbytes < min_bytes:
            continue
        try:
            replicated = realized.is_fully_replicated
        except Exception:
            continue
        if replicated:
            findings.append({
                "kind": "replicated_param",
                "tensor": _leaf_path(path),
                "bytes": nbytes,
                "bytes_wasted": int(nbytes * (nparts - 1) / nparts),
                "detail": f"partitioner assigned {nparts}-way sharding; "
                          "realized input is fully replicated",
            })
    return findings


def _grads_findings(compiled, params, expected_shardings, mesh, min_bytes):
    """Gradient outputs replicated where their parameter is partitioned.
    The step runner's first output is the grads tree (mirrors params)."""
    findings = []
    if params is None or expected_shardings is None:
        return findings
    try:
        out_shardings = compiled.output_shardings
        grads_sub = out_shardings[0]
        if grads_sub is None:
            return findings
        grads_leaves = jax.tree_util.tree_leaves(
            grads_sub, is_leaf=lambda x: hasattr(x, "is_fully_replicated")
        )
        exp_leaves = jax.tree_util.tree_leaves(expected_shardings)
        par = jax.tree_util.tree_flatten_with_path(params)[0]
    except Exception:
        return findings
    if len(grads_leaves) != len(par) or len(exp_leaves) != len(par):
        return findings
    for (path, leaf), want, got in zip(par, exp_leaves, grads_leaves):
        nparts = _spec_partitions(want, mesh)
        nbytes = int(getattr(leaf, "nbytes", 0) or 0)
        if nparts <= 1 or nbytes < min_bytes:
            continue
        try:
            replicated = got.is_fully_replicated
        except Exception:
            continue
        if replicated:
            findings.append({
                "kind": "replicated_grad_output",
                "tensor": _leaf_path(path),
                "bytes": nbytes,
                "bytes_wasted": int(nbytes * (nparts - 1) / nparts),
                "detail": f"parameter is {nparts}-way partitioned but its "
                          "gradient output is fully replicated",
            })
    return findings


def _loop_findings(hlo_text, census, cfg, mesh):
    """The PR-5 class: pipelined program with zero pp-axis permutes ->
    the tick loop is replicated across the pipeline axis."""
    from smdistributed_modelparallel_tpu.backend.topology import PP_AXIS

    findings = []
    pp = int(getattr(cfg, "pipeline_parallel_degree", 1) or 1) if cfg else 1
    mesh_pp = dict(mesh.shape).get(PP_AXIS, 1) if mesh is not None else 1
    if pp <= 1 or mesh_pp <= 1:
        return findings
    permutes = census.get("collective-permute", {})
    pp_permutes = permutes.get("axes", {}).get(PP_AXIS, {}).get("count", 0)
    if pp_permutes > 0:
        return findings
    carries = while_carries(hlo_text)
    carry = carries[0] if carries else None
    carry_bytes = carry["bytes"] if carry else 0
    findings.append({
        "kind": "replicated_loop_carry",
        "tensor": carry["op_name"] if carry else "(no while found)",
        "bytes": carry_bytes,
        "bytes_wasted": int(carry_bytes * (pp - 1) / pp),
        "detail": (
            f"pipeline_parallel_degree={pp} but the compiled program has "
            "0 pp-axis collective-permutes: GSPMD replicated the tick "
            "loop (every device computes every stage)"
        ),
    })
    return findings


def serving_kv_findings(compiled, mesh, cache_template=None,
                        min_bytes=1024):
    """Replication detector for the serving programs' paged KV pool
    (``smp.serving``): under a tp > 1 mesh every ``pool_key`` /
    ``pool_value`` output leaf must be tp-partitioned on its head axis
    (the ``PagedKVCache`` sharding contract) — a replicated pool
    multiplies the dominant serving HBM cost by tp. ``cache_template``
    (shape/dtype tree of the engine's cache) sizes the wasted bytes; the
    detector itself reads the compiled program's output shardings, so it
    audits fresh compiles and deserialized exec-cache hits alike."""
    from smdistributed_modelparallel_tpu.backend.topology import TP_AXIS

    findings = []
    tp = dict(mesh.shape).get(TP_AXIS, 1) if mesh is not None else 1
    if tp <= 1:
        return findings
    sizes = {}
    if cache_template is not None:
        try:
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                cache_template
            )[0]:
                name = _leaf_path(path)
                size = 1
                for d in leaf.shape:
                    size *= int(d)
                sizes[name] = size * jnp_dtype_bytes(leaf.dtype)
        except Exception:
            sizes = {}
    try:
        out_shardings = compiled.output_shardings
        leaves = jax.tree_util.tree_flatten_with_path(
            out_shardings, is_leaf=lambda x: hasattr(x, "is_fully_replicated")
        )[0]
    except Exception:
        return findings
    for path, sharding in leaves:
        name = _leaf_path(path)
        if "pool_key" not in name and "pool_value" not in name:
            continue
        try:
            replicated = sharding.is_fully_replicated
        except Exception:
            continue
        if not replicated:
            continue
        nbytes = 0
        for known, size in sizes.items():
            if name.endswith(known) or known.endswith(name):
                nbytes = size
                break
        if sizes and nbytes < min_bytes:
            continue
        findings.append({
            "kind": "replicated_kv_cache",
            "tensor": name,
            "bytes": nbytes,
            "bytes_wasted": int(nbytes * (tp - 1) / tp),
            "detail": (
                f"tensor_parallel_degree={tp} but the paged KV pool "
                "output is fully replicated (expected head-axis tp "
                "sharding)"
            ),
        })
    return findings


def jnp_dtype_bytes(dtype):
    try:
        import numpy as np

        return int(np.dtype(dtype).itemsize)
    except Exception:
        return 4


# ----------------------------------------------------------------------
# Low-precision (fp8) evidence census + the silently-upcast detector
# ----------------------------------------------------------------------

_F8_E4M3_RE = re.compile(r"f8e4m3", re.IGNORECASE)
_F8_E5M2_RE = re.compile(r"f8e5m2", re.IGNORECASE)
_HLO_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\S+)")
_HLO_SHAPE_RE = re.compile(r"\[([\d,]*)\]")


def _shape_elements(type_str):
    m = _HLO_SHAPE_RE.search(type_str)
    if not m or not m.group(1):
        return 1
    n = 1
    for d in m.group(1).split(","):
        n *= int(d)
    return n


def quant_report(hlo_text):
    """fp8 evidence census over the compiled HLO (matmul_precision:
    fp8 programs only — the block is additive, so every bf16
    fingerprint is unchanged).

    - ``native_f8_dots``: dot/convolution lines consuming f8-typed
      operands directly — what an f8-capable TPU MXU lowers to.
    - ``fp8_origin_dots``: dots whose operands are one-hop ``convert``
      upcasts OF an f8 value — XLA:CPU's legalization (it upcasts f8
      operands to f32 before the dot). The VALUES flowing through are
      still the quantized grid, so CPU-smoke programs count here.
    - ``f8_casts``: value-producing ops with an f8 result type, by
      format (e4m3 forward operands, e5m2 backward cotangents).

    A quantized program shows nonzero evidence in at least one bucket;
    all-zero under mode=fp8 is the ``quant_upcast`` finding."""
    casts = {"e4m3": 0, "e5m2": 0}
    f8_names = set()
    upcast_names = set()
    native_dots = 0
    origin_dots = 0
    for line in hlo_text.splitlines():
        m = _HLO_DEF_RE.match(line)
        if not m:
            continue
        name, out_type = m.group(1), m.group(2)
        out_f8 = bool(
            _F8_E4M3_RE.search(out_type) or _F8_E5M2_RE.search(out_type)
        )
        if out_f8:
            f8_names.add(name)
            if _F8_E4M3_RE.search(out_type):
                casts["e4m3"] += 1
            else:
                casts["e5m2"] += 1
        body = line[m.end(2):]
        if "convert(" in body and not out_f8:
            # Upcast convert FROM f8: operand type printed inline, or the
            # operand name is a known f8 producer.
            if (_F8_E4M3_RE.search(body) or _F8_E5M2_RE.search(body)
                    or any(
                        op in f8_names
                        for op in re.findall(r"%([\w.\-]+)", body)
                    )):
                upcast_names.add(name)
        if " dot(" in line or re.search(r"\bdot\(", body):
            ops = re.findall(r"%([\w.\-]+)", body)
            if (_F8_E4M3_RE.search(body) or _F8_E5M2_RE.search(body)
                    or any(op in f8_names for op in ops)):
                native_dots += 1
            elif any(op in upcast_names for op in ops):
                origin_dots += 1
    return {
        "native_f8_dots": native_dots,
        "fp8_origin_dots": origin_dots,
        "f8_casts": casts,
    }


def _largest_wide_dot(hlo_text):
    """(name, elements) of the biggest dot with non-f8 operands — the
    one the quant_upcast finding names as the likeliest missed seam."""
    best = None
    for line in hlo_text.splitlines():
        m = _HLO_DEF_RE.match(line)
        if not m:
            continue
        body = line[m.end(2):]
        if not re.search(r"\bdot\(", body):
            continue
        if _F8_E4M3_RE.search(line) or _F8_E5M2_RE.search(line):
            continue
        n = _shape_elements(m.group(2))
        if best is None or n > best[1]:
            best = (m.group(1), n)
    return best


def _quant_findings(quant_block, hlo_text):
    """The silently-upcast-matmul detector: mode=fp8 promised f8 dots
    but the compiled program carries ZERO fp8 evidence — no native f8
    dot, no fp8-origin dot, no f8 cast. That is the quantization
    equivalent of the missing_tp_ring finding: the knob was paid for
    (scale state threaded, cache keys split) and silently bought
    nothing."""
    findings = []
    if quant_block is None:
        return findings
    if (quant_block["native_f8_dots"] or quant_block["fp8_origin_dots"]
            or any(quant_block["f8_casts"].values())):
        return findings
    wide = _largest_wide_dot(hlo_text)
    return [{
        "kind": "quant_upcast",
        "tensor": wide[0] if wide else "*",
        "bytes_wasted": 0,
        "detail": (
            "matmul_precision=fp8 but the compiled program contains no "
            "f8 evidence at all (no f8-operand dot, no fp8-origin dot, "
            "no f8 cast) — every seam dispatched the full-precision "
            "path"
            + (f"; largest full-precision dot: %{wide[0]} "
               f"({wide[1]} elements)" if wide else "")
        ),
    }]


# ----------------------------------------------------------------------
# The audit itself
# ----------------------------------------------------------------------


class ProgramAudit:
    """Structured audit of one compiled step program."""

    def __init__(self, name, key, census, remat, memory, findings,
                 flops, bytes_accessed, hlo_sha256, config, zero=None,
                 recompute=None, tp_overlap=None, quant=None, op_index=None):
        self.name = name
        self.key = key
        self.census = census
        self.remat = remat
        self.memory = memory
        self.findings = findings
        self.flops = flops
        self.bytes_accessed = bytes_accessed
        self.hlo_sha256 = hlo_sha256
        self.config = config
        self.zero = zero
        self.recompute = recompute
        self.tp_overlap = tp_overlap
        self.quant = quant
        # ``op_records`` of the program: held in memory for whoever joins
        # a device trace to it (``op_index``), never persisted or hashed.
        self.op_index = op_index or {}
        self.fingerprint = self._fingerprint()
        self.fingerprint_hash = fingerprint_hash(self.fingerprint)

    # -- census queries -------------------------------------------------

    def collective_count(self, op, axis=None):
        ent = self.census.get(op, {})
        if axis is None:
            return ent.get("count", 0)
        return ent.get("axes", {}).get(axis, {}).get("count", 0)

    def collective_bytes(self, op, axis=None):
        ent = self.census.get(op, {})
        if axis is None:
            return ent.get("bytes", 0)
        return ent.get("axes", {}).get(axis, {}).get("bytes", 0)

    @property
    def replicated_bytes(self):
        return sum(f.get("bytes_wasted", 0) for f in self.findings)

    # -- export ---------------------------------------------------------

    def _fingerprint(self):
        fp = {
            "name": self.name,
            "key": self.key,
            "config": self.config,
            "collectives": self.census,
            "replicated": self.findings,
            "replicated_bytes": self.replicated_bytes,
            "remat": self.remat,
            "memory": self.memory,
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "hlo_sha256": self.hlo_sha256,
        }
        # Additive: only zero3 programs carry the block, so fingerprints
        # (and committed goldens) of every other program are unchanged.
        if self.zero is not None:
            fp["zero"] = self.zero
        # Additive likewise: only builds under a non-default recompute
        # plan carry the block — default-knob fingerprints are unchanged.
        if self.recompute is not None:
            fp["recompute"] = self.recompute
        # Additive likewise: only tp_overlap != "off" programs carry the
        # ring census/overlap-evidence block.
        if self.tp_overlap is not None:
            fp["tp_overlap"] = self.tp_overlap
        # Additive likewise: only matmul_precision=fp8 step programs
        # carry the fp8 evidence census.
        if self.quant is not None:
            fp["quant"] = self.quant
        return fp

    def as_dict(self):
        d = dict(self.fingerprint)
        d["fingerprint"] = self.fingerprint_hash
        return d


def _config_snapshot(cfg):
    if cfg is None:
        return {}
    snap = {
        "pipeline": getattr(cfg, "pipeline", None),
        "pp": getattr(cfg, "pipeline_parallel_degree", 1),
        "tp": getattr(cfg, "tensor_parallel_degree", 1),
        "v": getattr(cfg, "virtual_pipeline_degree", 1),
        "mb": getattr(cfg, "microbatches", 1),
    }
    # Additive (default omitted) so pre-zero3 fingerprints stay stable.
    sharded = getattr(cfg, "sharded_params", "none")
    if sharded and sharded != "none":
        snap["sharded_params"] = sharded
    # Additive likewise for the recompute knob (default "full" omitted).
    recompute = getattr(cfg, "recompute", "full")
    if recompute and recompute != "full":
        snap["recompute"] = recompute
    # Additive likewise for overlapped tp (default "off" omitted; the
    # CANONICAL mode, so a knob that cannot shape the program — tp=1,
    # cp>1 — never enters the snapshot).
    tp_overlap = _tp_overlap_mode(cfg)
    if tp_overlap != "off":
        snap["tp_overlap"] = tp_overlap
    # Additive likewise for the quant knob family (bf16/none omitted).
    try:
        from smdistributed_modelparallel_tpu import quant as _quant

        mode = _quant.matmul_precision_mode(cfg)
        if mode != "bf16":
            snap["matmul_precision"] = mode
        if _quant.kv_quant_mode() != "none":
            snap["kv_quant"] = _quant.kv_quant_mode()
        if _quant.decode_weights_mode() != "none":
            snap["decode_weights"] = _quant.decode_weights_mode()
    except Exception:  # pragma: no cover - defensive
        pass
    return snap


def fingerprint_hash(fp):
    """Short stable hash of the structured summary. Content-hash fields
    (``hlo_sha256``) are folded in; byte-identical programs hash equal,
    and any census/finding/memory movement changes it."""
    payload = json.dumps(fp, sort_keys=True, default=str)
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


def cache_key_hash(key):
    """Stable-enough digest of the step engine's compile-cache key (its
    repr covers treedefs, shapes, flags)."""
    return hashlib.sha256(repr(key).encode()).hexdigest()[:16]


def audit_compiled(name, compiled, key=None, params=None,
                   expected_param_shardings=None, mesh=None, cfg=None,
                   min_bytes=1024, publish=True, persist=True,
                   extra_findings_fn=None, tp_ring_expected=None):
    """Run the full audit over one compiled executable. Explicit calls
    always run (the ``SMP_HLO_AUDIT`` gate lives in ``maybe_audit``)."""
    from smdistributed_modelparallel_tpu.backend.state import state

    # Uninitialized framework (offline audits, e.g. of a deserialized
    # executable outside a training session): audit without mesh/config
    # attribution rather than refuse.
    try:
        mesh = mesh if mesh is not None else state.mesh
        cfg = cfg if cfg is not None else state.cfg
    except Exception:
        pass
    text = compiled.as_text()
    index = op_records(text, mesh=mesh)
    census = census_of(index)
    remat = remat_census(text)
    memory = memory_breakdown(compiled)
    zero = None
    if bool(getattr(cfg, "zero3_enabled", False)):
        zero = zero_report(text, mesh=mesh)
    # ``tp_ring_expected=False`` marks a program family the ring never
    # lowers into by design (the serving engine's decode/prefill
    # programs: decode-guarded attention, S=1 fallbacks) — no census, no
    # gauges, and crucially no missing_tp_ring false alarm for it.
    tp_overlap = None
    if _tp_overlap_mode(cfg) != "off" and tp_ring_expected is not False:
        tp_overlap = tp_overlap_report(text, mesh=mesh)
    # fp8 evidence census: training step programs only (serving/decode
    # programs never dispatch the fp8 seams — ``tp_ring_expected=False``
    # marks that family, exactly as for the ring detector).
    quant = None
    try:
        from smdistributed_modelparallel_tpu import quant as _quant_mod

        if (_quant_mod.matmul_precision_mode(cfg) != "bf16"
                and tp_ring_expected is not False):
            quant = quant_report(text)
    except Exception:  # pragma: no cover - defensive
        pass
    recompute = None
    try:
        from smdistributed_modelparallel_tpu.parallel import (
            remat_plan as _remat_plan,
        )

        recompute = _remat_plan.active_for(cfg)
    except Exception:  # pragma: no cover - defensive
        pass
    findings = []
    findings += _param_findings(
        params, expected_param_shardings, mesh, min_bytes
    )
    findings += _grads_findings(
        compiled, params, expected_param_shardings, mesh, min_bytes
    )
    findings += _loop_findings(text, census, cfg, mesh)
    findings += _tp_overlap_findings(tp_overlap, cfg, mesh)
    findings += _quant_findings(quant, text)
    if extra_findings_fn is not None:
        # Program-owner-specific detectors (e.g. the serving engine's
        # replicated-KV-pool check) — run on whatever executable is being
        # audited, fresh compile or deserialized cache hit.
        try:
            findings += list(extra_findings_fn(compiled, mesh) or [])
        except Exception as e:  # pragma: no cover - defensive
            logger.warning("[xray] %s: extra findings pass failed: %s",
                           name, e)
    flops = bytes_accessed = None
    try:
        from smdistributed_modelparallel_tpu.utils.profiling import cost_of

        flops, bytes_accessed = cost_of(compiled)
    except Exception:
        pass
    hlo_sha = hashlib.sha256(
        strip_source_metadata(text).encode()
    ).hexdigest()
    audit = ProgramAudit(
        name, key, census, remat, memory, findings, flops, bytes_accessed,
        hlo_sha, _config_snapshot(cfg), zero=zero, recompute=recompute,
        tp_overlap=tp_overlap, quant=quant, op_index=index,
    )
    if publish:
        # Unpublished audits stay out of the registry too: a verification
        # pass over a candidate executable (exec-cache load) must not
        # register a program that may then be rejected — republish()
        # registers it after the veto point.
        _register(audit)
        _publish(audit)
    if persist:
        _persist(audit)
    for f in findings:
        logger.warning(
            "[xray] %s: %s %s (%s wasted bytes): %s",
            name, f["kind"], f["tensor"], f.get("bytes_wasted"), f["detail"],
        )
    return audit


def maybe_audit(name, compiled, key=None, params=None,
                expected_param_shardings=None, extra_findings_fn=None,
                tp_ring_expected=None):
    """Post-compile hook from the step engine. ``SMP_HLO_AUDIT=off`` is a
    hard no-op (returns before touching the executable); failures are
    logged, never raised into the step path."""
    if not enabled():
        return None
    t0 = time.perf_counter()
    try:
        audit = audit_compiled(
            name, compiled, key=key, params=params,
            expected_param_shardings=expected_param_shardings,
            extra_findings_fn=extra_findings_fn,
            tp_ring_expected=tp_ring_expected,
        )
    except Exception as e:  # pragma: no cover - defensive
        logger.warning("[xray] hlo audit of %s failed: %s", name, e)
        return None
    _count_audit(audit, time.perf_counter() - t0)
    return audit


def _count_audit(audit, seconds):
    """Shared publication tail: the audit counters + the flight-recorder
    compile event carrying the program fingerprint. Used by both the
    fresh-compile path (maybe_audit) and the verified-cache-hit path
    (republish) so the two can never diverge."""
    telemetry.counter(
        "smp_hlo_audits_total", "completed post-compile HLO audits"
    ).inc()
    telemetry.counter(
        "smp_hlo_audit_seconds_total",
        "host seconds spent in post-compile HLO audits",
    ).inc(seconds)
    from smdistributed_modelparallel_tpu.utils.flight_recorder import (
        flight_recorder,
    )

    flight_recorder.record_compile(
        "hlo_audit", audit.name, seconds, fingerprint=audit.fingerprint_hash
    )


def republish(audit, seconds=0.0):
    """Re-publish a verified audit along the exact channels a fresh
    compile's ``maybe_audit`` uses: gauges, persistence, the audit
    registry, the audit counters, and the flight-recorder compile event
    with the program fingerprint. The executable-cache hit path calls
    this AFTER fingerprint verification so a warm start never silently
    bypasses the drift gates."""
    _register(audit)
    _publish(audit)
    _persist(audit)
    _count_audit(audit, seconds)


#: Latest audit per program name (``step``, ``step_pipeline_1f1b``, ...),
#: the program audited last at the end. Process-global: it outlives
#: ``smp.shutdown()``.
audits = {}


def _register(audit):
    audits.pop(audit.name, None)
    audits[audit.name] = audit


def op_index(name):
    """The op index (``op_records``) of the latest audit of program
    ``name``: ``{instruction name: {"phase", "scope"[, "op", "axis",
    "bytes"]}}``, the join key to a device trace's op names. ``{}`` when
    that program was never audited (``SMP_HLO_AUDIT=off``)."""
    audit = audits.get(name)
    return audit.op_index if audit is not None else {}


def step_program():
    """The name of the ``step*`` program audited last (``step``,
    ``step_pipeline_1f1b``, ...), or ``None``."""
    names = [n for n in audits if n.startswith("step")]
    return names[-1] if names else None


def seconds_by_scope(op_seconds, program=None):
    """The one join of a device's time to the program's scope tree.

    ``op_seconds``: ``{instruction name: self seconds}`` of one device,
    whatever produced them (a trace reduced by the benchmark, or by
    ``profiling.device_op_seconds``); ``program``: an op index
    (``op_records``), the name of an audited program, or ``None`` for the
    ``step*`` program audited last. Returns ``None`` without an index,
    else one record whose parts sum to ``busy_s``:

    - ``busy_s``: the sum of ``op_seconds``;
    - ``tree``: self seconds by scope path, the outermost-first tuple of
      an instruction's ``scopes``, so the paths under any prefix, or that
      hold any one scope, sum to that subtree;
    - ``user_only_s``: the seconds whose innermost scope is ``USER_SCOPE``:
      code the step function wrote itself (its part of ``tree``);
    - ``unscoped``: ``{"seconds", "top", "near"}``, the seconds under no
      scope (an instruction the index lacks among them), their ten largest
      instructions as ``[name, seconds, phase]``, and those seconds by the
      scope path the index says each is ``near`` (``()``: near none);
    - ``by_phase``: seconds by ``phase_of``'s phases (``other`` for a name
      the index lacks);
    - ``by_axis``: a collective's seconds by the mesh axis of its groups;
    - ``kernels``: seconds by the index's ``kernel`` mark.
    """
    if program is None:
        program = step_program()
    index = op_index(program) if isinstance(program, str) else program
    if not index:
        return None
    tree, by_phase, by_axis, kernels, loose, near = {}, {}, {}, {}, [], {}
    user_only = 0.0
    for name, seconds in op_seconds.items():
        rec = index.get(name) or {}
        phase = rec.get("phase", "other")
        by_phase[phase] = by_phase.get(phase, 0.0) + seconds
        if "axis" in rec:
            by_axis[rec["axis"]] = by_axis.get(rec["axis"], 0.0) + seconds
        if "kernel" in rec:
            kernels[rec["kernel"]] = kernels.get(rec["kernel"], 0.0) + seconds
        path = tuple(rec.get("scopes") or
                     ((rec["scope"],) if rec.get("scope") else ()))
        if not path:
            loose.append([name, seconds, phase])
            beside = tuple(rec.get("near", ()))
            near[beside] = near.get(beside, 0.0) + seconds
            continue
        tree[path] = tree.get(path, 0.0) + seconds
        if path[-1] == USER_SCOPE:
            user_only += seconds
    loose.sort(key=lambda row: -row[1])
    return {
        "busy_s": sum(op_seconds.values()),
        "tree": tree,
        "user_only_s": user_only,
        "unscoped": {"seconds": sum(row[1] for row in loose),
                     "top": loose[:10], "near": near},
        "by_phase": by_phase,
        "by_axis": by_axis,
        "kernels": kernels,
    }


def seconds_under(tree, *scopes):
    """Seconds of ``seconds_by_scope``'s ``tree`` in the paths that hold a
    scope starting with one of ``scopes`` (``"smp/attn/"``: every kind of
    attention, whatever layer or pipeline tick lies round it)."""
    return sum(seconds for path, seconds in tree.items()
               if any(scope.startswith(scopes) for scope in path))


def of_step_function(step_fn):
    """The audit of a ``@smp.step`` function's single compiled program —
    the stored post-compile audit when the pass ran, else computed on
    demand from the cached runner's executable. Returns None when no AOT
    executable exists (jit-fallback backends)."""
    runners = list(getattr(step_fn, "_cache", {}).values())
    if len(runners) != 1:
        raise ValueError(
            f"expected exactly one compiled program, found {len(runners)}"
        )
    runner = runners[0]
    audit = getattr(runner, "hlo_audit", None)
    if audit is not None:
        return audit
    compiled = runner.holder.get("compiled")
    if compiled is None:
        return None
    return audit_compiled(
        getattr(runner, "step_name", "step"), compiled,
        key=getattr(runner, "audit_key", None),
        publish=False, persist=False,
    )


# ----------------------------------------------------------------------
# Fingerprint diff
# ----------------------------------------------------------------------

#: The environment-stable fingerprint subset the golden regression gates
#: compare (memory/FLOPs/hashes move with jaxlib versions; these move
#: only when the program's parallel structure does).
SEMANTIC_FIELDS = ("config", "collectives", "replicated", "remat", "zero",
                   "recompute", "tp_overlap", "quant")


def diff(a, b, fields=None, remat_tol=0.02):
    """What changed between two fingerprints, as a list of
    ``{"field", "a", "b"}`` rows (empty == clean). ``fields`` restricts
    the comparison (e.g. ``SEMANTIC_FIELDS`` for the golden gates);
    ``remat_tol`` is the absolute tolerance on the remat fraction."""
    def picked(name):
        return fields is None or name in fields

    changes = []

    def add(field, va, vb):
        changes.append({"field": field, "a": va, "b": vb})

    if picked("config"):
        ca, cb = a.get("config", {}), b.get("config", {})
        for k in sorted(set(ca) | set(cb)):
            if ca.get(k) != cb.get(k):
                add(f"config.{k}", ca.get(k), cb.get(k))
    if picked("collectives"):
        colla, collb = a.get("collectives", {}), b.get("collectives", {})
        for op in sorted(set(colla) | set(collb)):
            ea = colla.get(op, {"count": 0, "bytes": 0, "axes": {}})
            eb = collb.get(op, {"count": 0, "bytes": 0, "axes": {}})
            axes = sorted(set(ea.get("axes", {})) | set(eb.get("axes", {})))
            for axis in axes:
                xa = ea.get("axes", {}).get(axis, {"count": 0, "bytes": 0})
                xb = eb.get("axes", {}).get(axis, {"count": 0, "bytes": 0})
                for k in ("count", "bytes"):
                    if xa.get(k, 0) != xb.get(k, 0):
                        add(f"collectives.{op}.{axis}.{k}",
                            xa.get(k, 0), xb.get(k, 0))
    if picked("replicated"):
        ra = a.get("replicated_bytes", 0)
        rb = b.get("replicated_bytes", 0)
        if ra != rb:
            add("replicated_bytes", ra, rb)
        na, nb = len(a.get("replicated", [])), len(b.get("replicated", []))
        if na != nb:
            add("replicated_findings", na, nb)
    if picked("remat"):
        fa = a.get("remat", {}).get("fraction", 0.0)
        fb = b.get("remat", {}).get("fraction", 0.0)
        if abs((fa or 0.0) - (fb or 0.0)) > remat_tol:
            add("remat.fraction", fa, fb)
    if picked("zero"):
        za, zb = a.get("zero") or {}, b.get("zero") or {}
        for k in sorted(set(za) | set(zb)):
            if za.get(k) != zb.get(k):
                add(f"zero.{k}", za.get(k), zb.get(k))
    if picked("recompute"):
        ra, rb = a.get("recompute") or {}, b.get("recompute") or {}
        for k in sorted(set(ra) | set(rb)):
            if ra.get(k) != rb.get(k):
                add(f"recompute.{k}", ra.get(k), rb.get(k))
    if picked("tp_overlap"):
        ta, tb = a.get("tp_overlap") or {}, b.get("tp_overlap") or {}
        for k in sorted(set(ta) | set(tb)):
            if ta.get(k) != tb.get(k):
                add(f"tp_overlap.{k}", ta.get(k), tb.get(k))
    if picked("quant"):
        # Evidence presence, not exact counts: cast/dot tallies move with
        # jaxlib fusion decisions; whether a bucket holds f8 evidence at
        # all only moves when the program's quantization does.
        qa, qb = a.get("quant") or {}, b.get("quant") or {}
        if bool(qa) != bool(qb):
            add("quant.present", bool(qa), bool(qb))
        elif qa:
            for k in ("native_f8_dots", "fp8_origin_dots"):
                if bool(qa.get(k)) != bool(qb.get(k)):
                    add(f"quant.{k}", qa.get(k), qb.get(k))
            fa_, fb_ = qa.get("f8_casts") or {}, qb.get("f8_casts") or {}
            for k in sorted(set(fa_) | set(fb_)):
                if bool(fa_.get(k)) != bool(fb_.get(k)):
                    add(f"quant.f8_casts.{k}", fa_.get(k), fb_.get(k))
    if picked("memory"):
        ma, mb = a.get("memory", {}), b.get("memory", {})
        for k in sorted(set(ma) | set(mb)):
            if ma.get(k) != mb.get(k):
                add(f"memory.{k}", ma.get(k), mb.get(k))
    if picked("flops"):
        if a.get("flops") != b.get("flops"):
            add("flops", a.get("flops"), b.get("flops"))
    if picked("hlo_sha256"):
        if a.get("hlo_sha256") != b.get("hlo_sha256"):
            add("hlo_sha256", a.get("hlo_sha256"), b.get("hlo_sha256"))
    return changes


# ----------------------------------------------------------------------
# Telemetry + persistence
# ----------------------------------------------------------------------


def _publish(audit):
    lab = dict(step=audit.name)
    for op, ent in audit.census.items():
        for axis, ax in ent["axes"].items():
            telemetry.gauge(
                "smp_hlo_collective_ops",
                "collective instruction count in the compiled program, "
                "by op kind and attributed mesh axis",
            ).labels(op=op, axis=axis, **lab).set(ax["count"])
            telemetry.gauge(
                "smp_hlo_collective_bytes",
                "per-device collective result bytes in the compiled "
                "program, by op kind and attributed mesh axis",
            ).labels(op=op, axis=axis, **lab).set(ax["bytes"])
    for kernel, phases in kernel_census(audit.op_index).items():
        for phase, count in phases.items():
            telemetry.gauge(
                "smp_kernel_calls",
                "Mosaic kernel instructions in the compiled program, by "
                "kernel name and phase of the step",
            ).labels(kernel=kernel, phase=phase, **lab).set(count)
    telemetry.gauge(
        "smp_hlo_replicated_bytes",
        "estimated per-device bytes wasted to detected replication",
    ).labels(**lab).set(audit.replicated_bytes)
    telemetry.gauge(
        "smp_hlo_replicated_findings",
        "sharding/replication findings in the compiled program",
    ).labels(**lab).set(len(audit.findings))
    telemetry.gauge(
        "smp_hlo_remat_fraction",
        "recomputed-FLOPs fraction of dot/conv instructions (static, "
        "structural-duplicate census)",
    ).labels(**lab).set(audit.remat.get("fraction", 0.0))
    for k, v in audit.memory.items():
        telemetry.gauge(
            "smp_hlo_memory_bytes",
            "XLA buffer-assignment bytes of the compiled program by class",
        ).labels(kind=k, **lab).set(v)
    if audit.zero is not None:
        from smdistributed_modelparallel_tpu.utils.telemetry import (
            record_zero3_xray,
        )

        record_zero3_xray(audit.name, audit.zero)
    if audit.tp_overlap is not None:
        from smdistributed_modelparallel_tpu.utils.telemetry import (
            record_tp_overlap_xray,
        )

        record_tp_overlap_xray(audit.name, audit.tp_overlap)


def _persist(audit):
    path = os.environ.get(AUDIT_PATH_ENV)
    if not path:
        return None
    path = telemetry._rank_path(path)
    data = {"version": 1, "programs": {}}
    try:
        with open(path, encoding="utf-8") as f:
            prev = json.load(f)
        if isinstance(prev, dict) and isinstance(prev.get("programs"), dict):
            data = prev
    except (OSError, ValueError):
        pass
    key_id = audit.name if not audit.key else f"{audit.name}@{audit.key}"
    data["programs"][key_id] = audit.as_dict()
    return _atomic_json_dump(data, path, "hlo-audit dump")
