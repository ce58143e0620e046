"""hlocost — static cost & memory model over lowered HLO (ISSUE 9).

hloaudit (tools/lint/hlo.py) answers *what* XLA emitted; this module
answers *how much* it costs.  Per flagship program, from the SAME
optimized-HLO text hloaudit lowers (lower once, audit twice), it
computes:

* **flops** — from ``dot``/``convolution`` shapes and contraction dims,
  weighted by execution multiplicity (fusion call sites, and while-loop
  trip counts taken from XLA's ``known_trip_count`` backend config);
* **HBM traffic** — bytes read/written at fusion boundaries: for every
  materializing instruction in a *scheduled* computation (entry, while
  bodies — NOT the interiors of fused computations, which stay in
  registers/cache), operand bytes + output bytes, trip-weighted;
* **peak live memory** — a liveness scan over the entry computation's
  instruction schedule (``is_scheduled=true`` HLO: text order IS the
  schedule).  Buffer sizes come from shapes/dtypes; pure-aliasing ops
  (``bitcast``/``tuple``/``get-tuple-element``) allocate nothing; outputs
  donated via ``input_output_alias`` write into their parameter's buffer
  and are excluded from the peak — so a LOST donation (the KV arena, the
  optimizer state) visibly inflates this number;
* **collective wire bytes per participant** — ring-algorithm cost per
  collective (all-reduce ``2(P-1)/P``, all-gather/reduce-scatter
  ``(P-1)/P``, permute ``1``) with ``P`` parsed from ``replica_groups``.
  The committed 2-way-DP train-step number is the f32 reference the
  ``compression="int8_ring"`` step's >= 3x win is held against.

Gated against committed per-program baselines under
``tools/lint/data/hlo/cost/`` (a ``COST00x`` finding family, the same
suppression/waiver contract and ``--update-baselines`` flow as the HLO
gate) are the numbers that move only when this repository's code
moves: **flops** (dot shapes and trip counts are ours), **donated
bytes** falling (a lost donation) and **wire bytes** (payload dtype and
group size are ours).  HBM traffic and peak live bytes depend on where
the compiler draws its fusion boundaries and how it schedules (the
train steps' moved 4-10% with one jax upgrade and no code), so they are
computed for their readers (the ``hlo_audit`` record, tools/autotune.py)
and not compared.

Scope limits (docs/static-analysis.md "Cost gate"): CPU lowerings with
tiny configs — the numbers gate *relative* drift; they are not latency
claims, and TPU-specific passes (Pallas custom-calls, ICI scheduling)
are invisible here.

Everything is purely textual — importing this module never imports jax.
"""

from __future__ import annotations

import dataclasses
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from .framework import Finding

__all__ = ["COST_CODES", "COST_SCHEMA", "COST_BASELINE_DIR", "TOLERANCES",
           "GATED_FIELDS", "parse_module", "summarize_cost",
           "cost_summaries", "diff_cost", "cost_gate_findings",
           "update_cost_baselines", "shape_bytes"]

#: committed per-program cost baselines, next to the structural ones
COST_BASELINE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "data", "hlo", "cost")

#: summary format version — a baseline with another version fails the
#: gate (COST001) instead of diffing garbage
COST_SCHEMA = 2

#: finding codes, one per metric (enumerated by ``--list-rules``)
COST_CODES = {
    "COST000": ("suppression-hygiene", "a cost-baseline 'suppress' entry "
                "without a reason, or naming an unknown metric code, is "
                "itself a finding and cannot be waived"),
    "COST001": ("program-set", "every audited program has a committed, "
                "parseable, same-schema cost baseline — and every "
                "baseline has a lowered program"),
    "COST002": ("flops", "analytic flops (dot/convolution shapes x "
                "contraction dims, trip-weighted) stay within tolerance "
                "of the baseline"),
    "COST004": ("lost-donation", "donated output bytes do not fall — a "
                "lost donation (KV arena, opt state) lands here with "
                "its byte cost"),
    "COST005": ("wire-bytes", "collective wire bytes per participant "
                "(ring model over replica_groups) stay within tolerance "
                "— the f32 DP baseline for int8-ring comparisons"),
}

#: relative drift tolerance per gated metric.  Lowerings are
#: deterministic for a fixed config, so these absorb only XLA-version
#: jitter; a config/mesh change moves the numbers far past them.
TOLERANCES = {
    "COST002": 0.02,   # flops
    "COST004": 0.02,   # donated bytes (falling only)
    "COST005": 0.01,   # wire bytes
}

#: the summary fields a baseline file holds and the gate compares;
#: ``hbm_bytes`` and ``peak_bytes`` are computed for their readers only
GATED_FIELDS = ("schema", "program", "flops", "donated_bytes", "wire_bytes")

#: bytes per element for HLO primitive types
_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "u2": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "tf32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
    "f8e4m3": 1, "f8e5m2": 1, "f8e4m3fn": 1, "f8e4m3b11fnuz": 1,
    "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
    # token/opaque carry no data
    "token": 0, "opaque": 0,
}

#: opcodes that never allocate: pure views over their operands
_ALIAS_OPS = frozenset({"bitcast", "tuple", "get-tuple-element"})

#: opcodes excluded from the HBM-traffic sum on top of the alias ops
#: (parameters are read by their consumers, not by themselves; constants
#: materialize at compile time)
_NO_TRAFFIC_OPS = _ALIAS_OPS | {"parameter", "constant"}

#: per-participant wire-cost factor of the ring algorithm, as a function
#: of group size P — the committed f32 reference model (int8-ring halves
#: the payload term, not the factor)
_WIRE_FACTOR = {
    "all-reduce": lambda p: 2.0 * (p - 1) / p,
    "all-reduce-start": lambda p: 2.0 * (p - 1) / p,
    "all-gather": lambda p: (p - 1) / p,
    "all-gather-start": lambda p: (p - 1) / p,
    "reduce-scatter": lambda p: (p - 1) / p,
    "all-to-all": lambda p: (p - 1) / p,
    "collective-broadcast": lambda p: (p - 1) / p,
    "collective-permute": lambda p: 1.0,
    "collective-permute-start": lambda p: 1.0,
}


# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------

_LEAF_SHAPE_RE = re.compile(r"([a-z]\w*)\[([\d,]*)\]")


def _leaf_bytes(dtype: str, dims_str: str) -> int:
    size = _DTYPE_BYTES.get(dtype)
    if size is None:
        return 0                      # unknown leaf type: count nothing
    n = 1
    for d in dims_str.split(","):
        if d:
            n *= int(d)
    return n * size


def shape_bytes(shape: str) -> int:
    """Buffer bytes of one HLO shape string — a leaf like
    ``f32[2,16]{1,0}`` or a tuple ``(s32[], f32[30,256]{1,0}, ...)``
    (layouts and ``/*index=N*/`` comments ignored)."""
    return sum(_leaf_bytes(dt, dims)
               for dt, dims in _LEAF_SHAPE_RE.findall(shape))


def _shape_dims(shape: str) -> List[int]:
    """Dims of a LEAF shape (first leaf if somehow a tuple)."""
    m = _LEAF_SHAPE_RE.search(shape)
    if m is None:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


def _prod(xs: Iterable[int]) -> int:
    out = 1
    for x in xs:
        out *= x
    return out


# ---------------------------------------------------------------------------
# HLO text -> module IR
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Instr:
    name: str
    opcode: str
    shape: str
    operands: Tuple[str, ...]         # referenced instruction names
    attrs: str                        # everything after the operand list
    is_root: bool


@dataclasses.dataclass
class Module:
    computations: Dict[str, List[Instr]]
    entry: Optional[str]
    #: (root output tuple index or None, parameter number) per donation
    aliases: List[Tuple[Optional[int], int]]
    num_partitions: int


_COMP_HEADER_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->")
_INSTR_HEAD_RE = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_ALIAS_ENTRY_RE = re.compile(r"\{([\d,\s]*)\}:\s*\((\d+)")
_TRIP_COUNT_RE = re.compile(r'known_trip_count\D{0,8}(\d+)')


def _split_rhs(rhs: str) -> Optional[Tuple[str, str, str, str]]:
    """``shape opcode(args), attrs`` -> (shape, opcode, args, attrs).
    Handles tuple shapes (balanced parens) and nested parens in args."""
    rhs = rhs.strip()
    if rhs.startswith("("):           # tuple shape: find its close paren
        depth, i = 0, 0
        while i < len(rhs):
            if rhs[i] == "(":
                depth += 1
            elif rhs[i] == ")":
                depth -= 1
                if depth == 0:
                    break
            i += 1
        shape, rest = rhs[:i + 1], rhs[i + 1:].lstrip()
    else:
        sp = rhs.find(" ")
        if sp < 0:
            return None
        shape, rest = rhs[:sp], rhs[sp + 1:].lstrip()
    op_end = rest.find("(")
    if op_end <= 0:
        return None
    opcode = rest[:op_end]
    if not re.fullmatch(r"[a-z][a-z0-9\-]*", opcode):
        return None
    depth, i = 0, op_end
    while i < len(rest):
        if rest[i] == "(":
            depth += 1
        elif rest[i] == ")":
            depth -= 1
            if depth == 0:
                break
        i += 1
    args = rest[op_end + 1:i]
    attrs = rest[i + 1:].lstrip(", ")
    return shape, opcode, args, attrs


def parse_module(text: str) -> Module:
    """Parse one optimized-HLO module's text into the cost IR.  Purely
    textual — no jax, no XLA."""
    comps: Dict[str, List[Instr]] = {}
    entry: Optional[str] = None
    cur: Optional[str] = None
    for line in text.splitlines():
        if line and not line[0].isspace():
            mh = _COMP_HEADER_RE.match(line)
            if mh:
                cur = mh.group(2)
                comps.setdefault(cur, [])
                if mh.group(1):
                    entry = cur
            continue
        mi = _INSTR_HEAD_RE.match(line)
        if mi is None or cur is None:
            continue
        parts = _split_rhs(mi.group(3))
        if parts is None:
            continue
        shape, opcode, args, attrs = parts
        comps[cur].append(Instr(
            name=mi.group(2), opcode=opcode, shape=shape,
            operands=tuple(_OPERAND_RE.findall(args)), attrs=attrs,
            is_root=bool(mi.group(1))))

    aliases: List[Tuple[Optional[int], int]] = []
    marker = text.find("input_output_alias={")
    if marker >= 0:
        # scan the balanced {...} block (entries nest one level deep)
        start = marker + len("input_output_alias=")
        depth, i = 0, start
        while i < len(text):
            if text[i] == "{":
                depth += 1
            elif text[i] == "}":
                depth -= 1
                if depth == 0:
                    break
            i += 1
        block = text[start:i + 1]
        for m in _ALIAS_ENTRY_RE.finditer(block):
            idx = m.group(1).strip()
            out_idx = int(idx.split(",")[0]) if idx else None
            aliases.append((out_idx, int(m.group(2))))

    mp = re.search(r"num_partitions=(\d+)", text)
    return Module(computations=comps, entry=entry, aliases=aliases,
                  num_partitions=int(mp.group(1)) if mp else 1)


# ---------------------------------------------------------------------------
# execution multiplicity (call graph + known trip counts)
# ---------------------------------------------------------------------------

_CALLEE_ATTR_RE = re.compile(
    r"(calls|body|condition|to_apply|branch_computations|"
    r"true_computation|false_computation)=\{?%?([\w.\-]+)"
    r"((?:,\s*%?[\w.\-]+)*)\}?")


def _callees(instr: Instr) -> List[Tuple[str, str]]:
    """(attr, computation) pairs an instruction calls."""
    out = []
    for m in _CALLEE_ATTR_RE.finditer(instr.attrs):
        out.append((m.group(1), m.group(2)))
        for extra in re.findall(r"%?([\w.\-]+)", m.group(3) or ""):
            out.append((m.group(1), extra))
    return out


def _trip_count(instr: Instr) -> int:
    m = _TRIP_COUNT_RE.search(instr.attrs)
    return int(m.group(1)) if m else 1


def computation_multiplicities(mod: Module) -> Dict[str, int]:
    """How many times each computation executes per program run:
    entry once; fusion/call/conditional/to_apply callees inherit their
    caller's count per call site; while bodies multiply by XLA's
    ``known_trip_count`` (1 when absent — an honest lower bound)."""
    mult: Dict[str, int] = {}
    if mod.entry is None:
        return mult
    frontier: List[Tuple[str, int]] = [(mod.entry, 1)]
    while frontier:
        comp, n = frontier.pop()
        mult[comp] = mult.get(comp, 0) + n
        for instr in mod.computations.get(comp, ()):
            trip = _trip_count(instr) if instr.opcode == "while" else 1
            for attr, callee in _callees(instr):
                if callee not in mod.computations:
                    continue
                k = n * trip if attr in ("body", "condition") else n
                frontier.append((callee, k))
    return mult


def _scheduled_computations(mod: Module) -> set:
    """Computations whose instructions materialize buffers (entry +
    while bodies/conditions + call/conditional targets) — fusion
    interiors and reduce to_apply regions live in registers and are
    reached only through their caller's boundary."""
    sched: set = set()
    if mod.entry is None:
        return sched
    frontier = [mod.entry]
    while frontier:
        comp = frontier.pop()
        if comp in sched:
            continue
        sched.add(comp)
        for instr in mod.computations.get(comp, ()):
            for attr, callee in _callees(instr):
                if attr in ("body", "condition", "branch_computations",
                            "true_computation", "false_computation") \
                        and callee in mod.computations:
                    frontier.append(callee)
                # plain call: scheduled too
                if attr == "to_apply" and instr.opcode == "call" \
                        and callee in mod.computations:
                    frontier.append(callee)
    return sched


# ---------------------------------------------------------------------------
# flops
# ---------------------------------------------------------------------------

_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_DIM_LABELS_RE = re.compile(r"dim_labels=(\w+)_(\w+)->(\w+)")


def _def_map(instrs: Sequence[Instr]) -> Dict[str, Instr]:
    return {i.name: i for i in instrs}

def _instr_flops(instr: Instr, defs: Dict[str, Instr]) -> int:
    """Analytic flops of one dot/convolution (0 for everything else):
    2 x output elements x contraction size."""
    if instr.opcode == "dot":
        out = _prod(_shape_dims(instr.shape))
        mc = _CONTRACT_RE.search(instr.attrs)
        contract = 1
        if mc and instr.operands:
            lhs = defs.get(instr.operands[0])
            dims = _shape_dims(lhs.shape) if lhs else []
            for ax in mc.group(1).split(","):
                if ax and int(ax) < len(dims):
                    contract *= dims[int(ax)]
        return 2 * out * contract
    if instr.opcode == "convolution":
        out_dims = _shape_dims(instr.shape)
        out = _prod(out_dims)
        kernel_elems, out_channels = 1, 1
        if len(instr.operands) >= 2:
            rhs = defs.get(instr.operands[1])
            kdims = _shape_dims(rhs.shape) if rhs else []
            kernel_elems = _prod(kdims)
            ml = _DIM_LABELS_RE.search(instr.attrs)
            if ml and kdims:
                o_pos = ml.group(2).find("o")
                if 0 <= o_pos < len(kdims):
                    out_channels = kdims[o_pos]
            elif kdims:
                out_channels = kdims[-1]
        return 2 * out * kernel_elems // max(out_channels, 1)
    return 0


def _computation_flops(mod: Module, comp: str,
                       seen: Optional[Dict[str, int]] = None) -> int:
    """Flops of ONE execution of a computation, recursing through every
    call edge (x trip count for while bodies)."""
    seen = {} if seen is None else seen
    if comp in seen:
        return seen[comp]
    seen[comp] = 0                    # cycles cannot occur in HLO; guard anyway
    instrs = mod.computations.get(comp, [])
    defs = _def_map(instrs)
    total = 0
    for instr in instrs:
        total += _instr_flops(instr, defs)
        trip = _trip_count(instr) if instr.opcode == "while" else 1
        for attr, callee in _callees(instr):
            if callee not in mod.computations:
                continue
            k = trip if attr in ("body", "condition") else 1
            total += k * _computation_flops(mod, callee, seen)
    seen[comp] = total
    return total


# ---------------------------------------------------------------------------
# HBM traffic
# ---------------------------------------------------------------------------

def _instr_traffic(instr: Instr, defs: Dict[str, Instr]) -> int:
    """Bytes read + written at one materializing instruction's boundary
    (unique operands counted once)."""
    read = sum(shape_bytes(defs[o].shape)
               for o in dict.fromkeys(instr.operands) if o in defs)
    return read + shape_bytes(instr.shape)


def _hbm_bytes(mod: Module, mult: Dict[str, int]) -> int:
    total = 0
    for comp in _scheduled_computations(mod):
        instrs = mod.computations.get(comp, [])
        defs = _def_map(instrs)
        n = mult.get(comp, 1)
        for instr in instrs:
            if instr.opcode in _NO_TRAFFIC_OPS:
                continue
            total += n * _instr_traffic(instr, defs)
    return total


# ---------------------------------------------------------------------------
# peak live memory (entry-schedule liveness, donation-aware)
# ---------------------------------------------------------------------------

def peak_live_bytes(mod: Module) -> int:
    """Max over the entry schedule of the live-buffer byte sum.

    Model: each non-alias instruction allocates its output buffer at its
    schedule index and frees it after its last (alias-transitive) use.
    Entry parameters and the root's buffers are live for the WHOLE
    program — the caller owns argument and result buffers across the
    call, which is the runtime contract jax dispatch actually has.
    Outputs aliased to a parameter via ``input_output_alias`` allocate
    NOTHING — they write into the donated parameter in place — which is
    exactly why a lost donation inflates this number by the donated
    buffer's size: the result needs its own allocation on top of the
    still-live argument."""
    if mod.entry is None:
        return 0
    instrs = mod.computations.get(mod.entry, [])
    defs = _def_map(instrs)
    index = {i.name: k for k, i in enumerate(instrs)}

    # alias-transitive underlying allocations of each value
    underlying: Dict[str, Tuple[str, ...]] = {}
    for instr in instrs:
        if instr.opcode in _ALIAS_OPS:
            u: List[str] = []
            for o in instr.operands:
                u.extend(underlying.get(o, (o,) if o in defs else ()))
            underlying[instr.name] = tuple(dict.fromkeys(u))
        else:
            underlying[instr.name] = (instr.name,)

    last_use: Dict[str, int] = {}
    root: Optional[Instr] = None
    for k, instr in enumerate(instrs):
        if instr.is_root:
            root = instr
        for o in instr.operands:
            for b in underlying.get(o, ()):
                last_use[b] = max(last_use.get(b, 0), k)

    end = len(instrs)
    # donated outputs: the producing buffer writes into its parameter
    donated_bufs: set = set()
    if root is not None and mod.aliases:
        root_ops = root.operands
        for out_idx, _param_no in mod.aliases:
            src = None
            if out_idx is None:
                src = root.name
            elif out_idx < len(root_ops):
                src = root_ops[out_idx]
            if src is not None:
                donated_bufs.update(underlying.get(src, ()))
    if root is not None:
        for b in underlying.get(root.name, ()):
            last_use[b] = end         # result buffers: live to the end

    delta = [0] * (end + 2)
    for k, instr in enumerate(instrs):
        if instr.opcode in _ALIAS_OPS:
            continue
        size = shape_bytes(instr.shape)
        if size <= 0:
            continue
        if instr.name in donated_bufs and instr.opcode != "parameter":
            continue                  # writes into its parameter in place
        if instr.opcode == "parameter":
            start, stop = 0, end      # caller-owned across the call
        else:
            start, stop = k, last_use.get(instr.name, k)
        delta[start] += size
        delta[stop + 1] -= size
    peak = live = 0
    for d in delta:
        live += d
        peak = max(peak, live)
    return peak


def donated_bytes(mod: Module) -> int:
    """Bytes of entry outputs aliased to parameters — what donation
    saves per dispatch.  The structural gate (HLO005) counts the alias
    ENTRIES; this weighs them: a lost KV-arena or opt-state donation
    means the result needs its own allocation on top of the still-live
    argument, inflating peak live memory by exactly this many bytes."""
    if mod.entry is None or not mod.aliases:
        return 0
    instrs = mod.computations.get(mod.entry, [])
    defs = _def_map(instrs)
    root = next((i for i in instrs if i.is_root), None)
    if root is None:
        return 0
    total = 0
    for out_idx, _param_no in mod.aliases:
        if out_idx is None:
            total += shape_bytes(root.shape)
        elif out_idx < len(root.operands):
            op = defs.get(root.operands[out_idx])
            if op is not None:
                total += shape_bytes(op.shape)
    return total


# ---------------------------------------------------------------------------
# collective wire bytes
# ---------------------------------------------------------------------------

_REPLICA_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")


def _group_size(instr: Instr, mod: Module) -> int:
    m = _REPLICA_GROUPS_RE.search(instr.attrs)
    if m:
        return len(m.group(1).split(","))
    return max(mod.num_partitions, 1)


def wire_bytes_per_participant(mod: Module, mult: Dict[str, int]) -> int:
    """Ring-model wire bytes one participant sends, summed over every
    collective (trip-weighted).  ``*-done`` ops carry no new payload."""
    total = 0.0
    for comp, instrs in mod.computations.items():
        n = mult.get(comp, 0)
        if n == 0:
            continue
        defs = _def_map(instrs)
        for instr in instrs:
            factor = _WIRE_FACTOR.get(instr.opcode)
            if factor is None:
                continue
            p = _group_size(instr, mod)
            if p <= 1:
                continue
            if instr.opcode == "reduce-scatter":
                payload = sum(shape_bytes(defs[o].shape)
                              for o in dict.fromkeys(instr.operands)
                              if o in defs)
            else:
                payload = shape_bytes(instr.shape)
            total += n * factor(p) * payload
    return int(round(total))


# ---------------------------------------------------------------------------
# the per-program cost summary
# ---------------------------------------------------------------------------

def summarize_cost(text: str, program: str) -> Dict:
    """One optimized-HLO module's analytic cost summary.  Deterministic
    for a fixed lowering; :data:`GATED_FIELDS` of it are committed."""
    mod = parse_module(text)
    mult = computation_multiplicities(mod)
    flops = _computation_flops(mod, mod.entry) if mod.entry else 0
    return {
        "schema": COST_SCHEMA,
        "program": program,
        "flops": int(flops),
        "hbm_bytes": int(_hbm_bytes(mod, mult)),
        "peak_bytes": int(peak_live_bytes(mod)),
        "donated_bytes": int(donated_bytes(mod)),
        "wire_bytes": wire_bytes_per_participant(mod, mult),
    }


def cost_summaries(texts: Dict[str, str]) -> Dict[str, Dict]:
    """Cost summary per program from already-lowered HLO texts — the
    "lower once, audit twice" half: callers hand over the SAME texts
    the structural gate summarizes."""
    return {name: summarize_cost(text, name)
            for name, text in texts.items()}


# ---------------------------------------------------------------------------
# gate: baselines, tolerance diff, update flow
# ---------------------------------------------------------------------------

def diff_cost(program: str, baseline: Dict, current: Dict,
              path: str) -> List[Finding]:
    """Named COST00x finding per metric drifted past its tolerance."""
    from .hlo import _baseline_suppressions
    waived, findings = _baseline_suppressions(
        baseline, path, COST_CODES, "COST000")

    def fnd(code: str, msg: str) -> None:
        if code in waived:
            return
        findings.append(Finding(path, 1, 0, code,
                                f"[{program}] {msg} — if intentional, "
                                f"re-baseline with 'python -m tools.lint "
                                f"--hlo --update-baselines'"))

    if baseline.get("schema") != current.get("schema"):
        findings.append(Finding(
            path, 1, 0, "COST001",
            f"[{program}] cost baseline schema {baseline.get('schema')!r} "
            f"does not match the auditor's {current.get('schema')!r} — "
            f"regenerate with --update-baselines"))
        return findings

    def rel(code: str, field: str, what: str, unit: str = "") -> None:
        b, c = baseline.get(field), current.get(field)
        if not isinstance(b, (int, float)) or isinstance(b, bool):
            fnd(code, f"baseline {field!r} is {b!r}, not a number — "
                      f"regenerate with --update-baselines")
            return
        tol = TOLERANCES[code]
        drift = abs((c or 0) - b) / max(abs(b), 1.0)
        if drift > tol:
            pct = 100.0 * ((c or 0) - b) / max(abs(b), 1.0)
            fnd(code, f"{what} drifted {b:,}{unit} -> {c:,}{unit} "
                      f"({pct:+.1f}%, tolerance {tol:.0%})")

    rel("COST002", "flops", "analytic flops")
    b, c = baseline.get("donated_bytes"), current.get("donated_bytes")
    if isinstance(b, (int, float)) and (c or 0) < b and \
            (b - (c or 0)) / max(b, 1.0) > TOLERANCES["COST004"]:
        fnd("COST004",
            f"donated output bytes dropped {b:,} B -> {c or 0:,} B — a "
            f"donation was LOST: the result (KV arena / opt state) now "
            f"needs its own allocation on top of the still-live "
            f"argument, inflating peak live memory by {b - (c or 0):,} B "
            f"every dispatch")
    rel("COST005", "wire_bytes", "collective wire bytes/participant",
        " B")
    return findings


def cost_gate_findings(summaries: Dict[str, Dict],
                       baseline_dir: Optional[str] = None) -> List[Finding]:
    """Diff cost summaries against the committed baselines; [] = clean.
    Shares the structural gate's program-set core
    (hlo.gate_findings_dir — misses loud in both directions, COST001)."""
    from .hlo import gate_findings_dir
    return gate_findings_dir(summaries,
                             baseline_dir or COST_BASELINE_DIR,
                             "COST001", "cost baseline", diff_cost,
                             "numbers")


def update_cost_baselines(summaries: Dict[str, Dict],
                          baseline_dir: Optional[str] = None) -> str:
    """Write the cost summaries as the new baselines via the shared
    update core (hlo.update_baselines_dir: suppress blocks preserved,
    stale programs pruned loudly, human-readable metric diff
    returned)."""
    from .hlo import update_baselines_dir
    return update_baselines_dir(
        summaries, baseline_dir or COST_BASELINE_DIR, "COST001",
        "cost baseline", diff_cost,
        lambda s: (f"{s['flops']:,} flops, donated "
                   f"{s['donated_bytes']:,} B, wire {s['wire_bytes']:,} B"),
        "cost unchanged", GATED_FIELDS)
