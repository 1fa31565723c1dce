"""Which phase of a program each of its device operations belongs to.

The program names its phases with ``jax.named_scope`` (``hash``,
``row_gather``, ``lane_select``, ``tag_match``, ...). XLA keeps the scope
path in each instruction's metadata, which the compiled text
(``jax.stages.Compiled.as_text()``) shows:
``%fusion.1 = ... metadata={op_name="jit(cuckoo_query)/row_gather/..."}``.
A device trace names an operation by its instruction name alone
(``fusion.1``, see ``trace.op_name``), so the map from operation to phase
comes from the compiled text of the program that ran, at the shapes it ran
with.

The rule:

* an instruction's phase is the innermost name of :data:`PHASES` on its
  ``op_name`` path; ``-`` where the path holds none or the instruction has
  no metadata (the copies and slices the compiler adds);
* a fusion (or any instruction that ``calls=`` a computation) is charged to
  the phase of that computation's root instruction; a root with no metadata
  (the tuple of a multi-output fusion) takes the phase of its first operand
  that has one.
"""

from __future__ import annotations

import re

PHASES = ("hash", "row_gather", "lane_select", "tag_match", "bulk_place",
          "orient", "residue")
NONE = "-"
QUERY_PROGRAM = "jit_cuckoo_query"

_INST = re.compile(r"^\s+(ROOT )?%([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")


def _innermost(op_name: str) -> str:
    for part in reversed(op_name.split("/")):
        if part in PHASES:
            return part
    return NONE


def phases(hlo_text: str) -> dict:
    """Instruction name -> phase, for every instruction of the compiled
    text of one program (names are unique within a program)."""
    roots, insts, current = {}, {}, None
    for line in hlo_text.splitlines():
        if line.startswith(("%", "ENTRY %")) and line.rstrip().endswith("{"):
            current = line.split("%", 1)[1].split(" ", 1)[0]
            continue
        m = _INST.match(line)
        if m is None or current is None:
            continue
        is_root, name, rest = m.groups()
        op_name, calls = _OP_NAME.search(rest), _CALLS.search(rest)
        insts[name] = (op_name.group(1) if op_name else None,
                       calls.group(1) if calls else None,
                       _OPERAND.findall(rest.split(", metadata=", 1)[0]))
        if is_root:
            roots[current] = name

    memo = {}

    def phase(name: str) -> str:
        if name not in memo:
            op_name, calls, _ = insts[name]
            memo[name] = NONE      # a cycle (never in valid HLO) reads NONE
            if calls in roots:
                memo[name] = root_phase(roots[calls])
            elif op_name is not None:
                memo[name] = _innermost(op_name)
        return memo[name]

    def root_phase(root: str) -> str:
        op_name, calls, operands = insts[root]
        if op_name is not None or calls is not None:
            return phase(root)
        return next((p for p in map(phase, operands) if p != NONE), NONE)

    return {name: phase(name) for name in insts}


def phase_seconds(ops, program: str, table: dict):
    """Device seconds per phase of ``program``'s operations among ``ops``
    (``[["<program>/<operation>", seconds], ...]``, as ``trace.reduce``
    lists them). None when an operation is not an instruction of ``table``:
    then the text is not that of the program that ran."""
    out = {}
    for qualified, seconds in ops:
        owner, _, op = qualified.partition("/")
        if owner != program:
            continue
        if op not in table:
            return None
        out[table[op]] = out.get(table[op], 0.0) + seconds
    return out


def query_text(run: dict):
    """Compiled text of the cell's query program at the traffic's batch,
    compiled through the program's handle (from the persistent cache where
    the run filled it); kept in ``run["query_hlo"]``. None where the
    program has no ``FilterHandle.compiled``."""
    if "query_hlo" not in run:
        import jax
        import jax.numpy as jnp

        from drivers.common import make_handle

        handle = make_handle(run["config"])
        keys = jax.ShapeDtypeStruct((run["traffic"]["batch"], 2), jnp.uint32)
        run["query_hlo"] = (handle.compiled("query", keys).as_text()
                            if hasattr(handle, "compiled") else None)
    return run["query_hlo"]


def query_ns_per_key(run: dict, wanted) -> float | None:
    """Device nanoseconds per key answered in the traced window spent in the
    query program's operations whose phase is in ``wanted``.

    Reads the trace's top operations (``trace.reduce``'s ``top_ops``, the
    ten longest of the window): a phase's operations outside them are not
    counted. None without a trace, without the named query program (a
    program that does not name it), or when the compiled text does not hold
    every operation the trace lists.
    """
    t, keys = run.get("trace"), (run.get("traced_ops") or {}).get("query")
    if not t or not keys or t["module_s"].get(QUERY_PROGRAM, 0.0) <= 0:
        return None
    text = query_text(run)
    if text is None:
        return None
    seconds = phase_seconds(t["top_ops"], QUERY_PROGRAM, phases(text))
    if seconds is None:
        return None
    return 1e9 * sum(s for p, s in seconds.items() if p in wanted) / keys
