"""Compiled-HLO communication audit.

Deterministic, hardware-independent accounting of a jitted step function's
collective traffic: per-collective op counts and bytes from the optimized
HLO text, plus compiler cost/memory analysis. This is the measurement the
labs already trusted ("compiled psum/all-gather volume transfers to
hardware; vCPU wall time does not" — ``tools/kernel_lab.py``), promoted to
a library and fixed to recognize ASYNC collective forms: XLA may emit
``all-gather-start``/``all-gather-done`` pairs instead of the sync op on
some backend/flag combinations, and the old anchor (``all-gather(``)
silently reported 0 bytes for those (ADVICE r5).

Parsing contract: only DEFINING instructions are counted (``= shape op(``) —
a loose match would also count every consumer line naming the collective's
result — and ``-done`` halves of async pairs never match (the op name must
be followed by ``(`` or ``-start(``). For async starts that define a tuple,
the traffic-carrying shape is taken as the largest tuple element (the
result; operand aliases and ``u32[]`` context scalars are smaller).

Reduce-scatter is the one family whose DEFINING shape understates the
wire: the sync form's result is the 1/N owned slice of the summed operand,
so billing the result alone undercounts the traffic N-fold (every element
of the full operand crosses the interconnect exactly as in an all-reduce's
reduce phase). For ``reduce-scatter``/``all-reduce-scatter`` the billed
bytes are therefore the max shape atom across the instruction's operands
as well as its result, with the same dtype-exact sub-byte rule
(``(n*bits+7)//8``) as everywhere else. The HLO text jax 0.9.0 emits names
operands without their shapes (``reduce-scatter(%bitcast)``), so an
operand's shape is looked up from its own defining line earlier in the same
computation; shapes printed inline (older text) are read where they stand.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, Optional, Sequence, Tuple

import numpy as np

# collective op families, longest-prefix first so e.g. "all-gather" never
# swallows "all-to-all"'s hyphenated cousins
COLLECTIVE_OPS = (
    "all-reduce-scatter",  # historical alias, keep before all-reduce
    "reduce-scatter",
    "all-reduce",
    "all-gather",
    "ragged-all-to-all",
    "all-to-all",
    "collective-broadcast",
    "collective-permute",
)

# element widths in BITS: sub-byte dtypes (s4/u4, the native int4 planes)
# really cost half a byte per element on the wire, and counting them as u8
# elements would understate a quantized wire's measured reduction by 2x
_DTYPE_BITS = {
    "s4": 4, "u4": 4,
    "pred": 8, "s8": 8, "u8": 8,
    "f8e4m3fn": 8, "f8e5m2": 8, "f8e4m3b11fnuz": 8, "f8e4m3fnuz": 8,
    "f8e5m2fnuz": 8,
    "s16": 16, "u16": 16, "f16": 16, "bf16": 16,
    "s32": 32, "u32": 32, "f32": 32,
    "s64": 64, "u64": 64, "f64": 64, "c64": 64,
    "c128": 128,
}

# defining instruction: "<name> = <shape> <op>[-start](", where <shape> is a
# single "dtype[dims]{layout}" or a tuple "(shape, shape, ...)"
_DEFINING_RE = re.compile(
    r"=\s+(?P<shape>\([^=]*?\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"(?P<op>%s)(?P<start>-start)?\(" % "|".join(COLLECTIVE_OPS)
)
_SHAPE_ATOM_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
# any instruction's "<name> = <shape>" head, and operand references
_ANY_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*"
    r"(?P<shape>\([^=]*?\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s")
_OPERAND_REF_RE = re.compile(r"%([\w.\-]+)")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_SCOPE_RE = re.compile(r"(ssn_[\w\-.]+)")

# ops whose defining shape is a 1/N slice of the moved payload: bill the
# operand list too (sync reduce-scatter results understate traffic N-fold)
_FULL_OPERAND_OPS = frozenset({"reduce-scatter", "all-reduce-scatter"})

# ops whose tuple result IS the payload, one element per peer: XLA lowers a
# tiled shard_map all-to-all to "(T[n,..]{..}, ...) all-to-all(T[n,..] a, ...)"
# with axis_size equal pieces — max-element billing would undercount the
# moved buffer axis_size-fold, so these sum every tuple element instead
_SUM_TUPLE_OPS = frozenset({"all-to-all", "ragged-all-to-all"})


def _atom_bytes(dtype: str, dims: str) -> int:
    bits = _DTYPE_BITS.get(dtype)
    if bits is None:  # token/opaque/tuple-in-tuple: carries no payload here
        return 0
    shape = [int(d) for d in dims.split(",") if d]
    n = int(np.prod(shape)) if shape else 1
    return (n * bits + 7) // 8  # dtype-exact: s4/u4 pack two per byte


def _shape_bytes(shape: str) -> int:
    """Bytes of the traffic-carrying result shape (largest tuple element)."""
    atoms = _SHAPE_ATOM_RE.findall(shape)
    if not atoms:
        return 0
    return max(_atom_bytes(dt, dims) for dt, dims in atoms)


def _shape_bytes_sum(shape: str) -> int:
    """Bytes summed over every shape atom (per-peer tuple pieces)."""
    return sum(_atom_bytes(dt, dims) for dt, dims in
               _SHAPE_ATOM_RE.findall(shape))


def collective_stats(hlo_text: str) -> Dict:
    """Per-collective counts/bytes (sync and async forms) from HLO text.

    Returns ``{"ops": {op: {"count", "bytes"}}, "total_bytes", "by_scope",
    "by_table"}`` where ``op`` is the base HLO name (``-start`` folded in)
    and ``by_scope`` groups bytes under the first non-table ``ssn_*`` label
    found in the instruction's ``op_name`` metadata (see the
    ``jax.named_scope`` labels in ``parallel/transfer.py`` /
    ``parallel/store.py``). ``ssn_tbl_*`` labels are the per-table
    attribution scopes the trainers wrap around whole pull/push call sites
    (outer scopes, so they co-occur with the collective's own label on one
    ``op_name``); they are routed to ``by_table`` keyed by the table name so
    the placement/bench stack can split exchange bytes per table without
    disturbing the existing per-collective scope keys.
    """
    ops: Dict[str, Dict[str, int]] = {}
    by_scope: Dict[str, int] = {}
    by_table: Dict[str, int] = {}
    total = 0
    defs: Dict[str, str] = {}  # instruction name -> shape, this computation
    for line in hlo_text.splitlines():
        if line.rstrip().endswith("{"):  # a computation header: new scope
            defs = {}
        d = _ANY_DEF_RE.match(line)
        if d is not None:
            defs[d.group("name")] = d.group("shape")
        m = _DEFINING_RE.search(line)
        if m is None:
            continue
        nbytes = _shape_bytes(m.group("shape"))
        op = m.group("op")
        if op in _SUM_TUPLE_OPS:
            nbytes = _shape_bytes_sum(m.group("shape"))
            if m.group("start"):
                # async start tuples carry operand aliases next to the
                # results; summing both would double-bill the payload
                nbytes //= 2
        if op in _FULL_OPERAND_OPS:
            # operand shapes sit inside the call parens; stop before the
            # metadata blob so op_name strings can't smuggle in fake atoms
            tail = line[m.end():]
            cut = tail.find("metadata=")
            if cut != -1:
                tail = tail[:cut]
            nbytes = max(nbytes, _shape_bytes(tail))
            # operands named without shapes: the call parens close at the
            # first ")", and each %name resolves to its defining shape
            for ref in _OPERAND_REF_RE.findall(tail.split(")", 1)[0]):
                nbytes = max(nbytes, _shape_bytes(defs.get(ref, "")))
        entry = ops.setdefault(op, {"count": 0, "bytes": 0})
        entry["count"] += 1
        entry["bytes"] += nbytes
        total += nbytes
        name_m = _OP_NAME_RE.search(line)
        if name_m:
            scoped = False
            for scope_m in _SCOPE_RE.finditer(name_m.group(1)):
                scope = scope_m.group(1)
                if scope.startswith("ssn_tbl_"):
                    tbl = scope[len("ssn_tbl_"):]
                    by_table[tbl] = by_table.get(tbl, 0) + nbytes
                elif not scoped:
                    # first non-table label = the collective's own scope
                    by_scope[scope] = by_scope.get(scope, 0) + nbytes
                    scoped = True
    return {"ops": ops, "total_bytes": total, "by_scope": by_scope,
            "by_table": by_table}


def collective_bytes(hlo_text: str, op_pattern: Optional[str] = None) -> int:
    """Total bytes moved by collectives whose BASE op name matches
    ``op_pattern`` (regex, fullmatch; ``None`` = every collective). Async
    ``-start`` forms count under their base name."""
    stats = collective_stats(hlo_text)
    if op_pattern is None:
        return stats["total_bytes"]
    pat = re.compile(op_pattern)
    return sum(
        entry["bytes"]
        for op, entry in stats["ops"].items()
        if pat.fullmatch(op)
    )


def _normalize_cost(cost) -> Dict[str, float]:
    """``compiled.cost_analysis()`` returns a dict or a 1-list of dicts
    depending on jax version; keep the headline keys only."""
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    out = {}
    for key in ("flops", "bytes accessed", "transcendentals"):
        if key in cost:
            out[key.replace(" ", "_")] = float(cost[key])
    return out


_MEMORY_ATTRS = (
    "peak_memory_in_bytes",
    "argument_size_in_bytes",
    "output_size_in_bytes",
    "temp_size_in_bytes",
    "alias_size_in_bytes",
    "generated_code_size_in_bytes",
)


def _normalize_memory(mem) -> Dict[str, int]:
    out = {}
    for attr in _MEMORY_ATTRS:
        v = getattr(mem, attr, None)
        if v is not None:
            out[attr] = int(v)
    return out


def audit_compiled(compiled) -> Dict:
    """Audit an already-compiled executable (``jit(f).lower(...).compile()``)."""
    report = collective_stats(compiled.as_text())
    try:
        report["cost"] = _normalize_cost(compiled.cost_analysis())
    except Exception as e:  # some backends don't implement it
        report["cost"] = {"error": str(e)}
    try:
        report["memory"] = _normalize_memory(compiled.memory_analysis())
    except Exception as e:
        report["memory"] = {"error": str(e)}
    return report


def audit_step(fn, *args, **kwargs) -> Dict:
    """Lower+compile ``fn(*args, **kwargs)`` and audit the optimized HLO.

    ``fn`` may be a plain callable or an existing ``jax.jit`` wrapper (it is
    lowered as-is when it already has ``.lower``). Compilation only — nothing
    executes, so donated/sharded arguments are safe to pass.
    """
    import jax

    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    return audit_compiled(jitted.lower(*args, **kwargs).compile())


def compiled_collective_bytes(fn, args: Sequence, op_pattern: str) -> int:
    """Bytes moved by collectives matching ``op_pattern`` in the optimized
    HLO of ``jit(fn)(*args)`` — the hardware-transferable traffic number
    (ICI volume scales the same way the compiled shapes do). Recognizes both
    sync (``all-gather(``) and async (``all-gather-start(``) forms; pass the
    base op names, e.g. ``"all-gather|all-reduce"``.
    """
    import jax

    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    hlo = jitted.lower(*args).compile().as_text()
    return collective_bytes(hlo, op_pattern)
