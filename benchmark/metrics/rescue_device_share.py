"""Percent of the window's device busy time spent in the overlong rescue
(``models/wordcount.py`` ``rescued_table``, ``ops/rescue.py``): the device
time of the rescue's ``lax.cond(overlong > 0)``, which is its own self time
and that of every op nested in it (the tier cond, the gather of each poison
position's window, the re-hash and the merge), over the busy time, averaged
over the chips.

The rescue's conditional is found by its HLO, as the trace prints it: one
branch takes the chunk (a 1-D ``u8`` array) beside the poison rows (a 1-D
``u32`` array), the other only the table (no ``u8`` at all: the chunk's
overlong tokens are accounted, not re-hashed).  The tier cond nested in it,
and the map's spill fallback, hand the chunk to both branches, so neither
is taken for it.  A trace without such a conditional reads nothing."""

import re

_U8_1D = re.compile(r"\bu8\[\d+\]")
_U32_1D = re.compile(r"\bu32\[\d+\]")


def _branch_operands(hlo: str) -> list[str]:
    """The branch operands of a ``conditional``'s text (its first operand,
    the branch index, left out)."""
    begin = hlo.find(" conditional(")
    if begin < 0:
        return []
    begin += len(" conditional(")
    parts, depth = [], 0
    for i in range(begin, len(hlo)):
        c = hlo[i]
        if c in "([{":
            depth += 1
        elif c in ")]}" and depth:
            depth -= 1
        elif c == ")" or (c == "," and not depth):
            parts.append(hlo[begin:i])
            if c == ")":
                return parts[1:]
            begin = i + 1
    return []


def is_rescue(op) -> bool:
    if op.opcode != "conditional":
        return False
    branches = _branch_operands(op.hlo)
    return (any(_U8_1D.search(b) and _U32_1D.search(b) for b in branches)
            and any("u8[" not in b for b in branches))


def read(run):
    t = run.trace
    if t is None or not t.busy_s:
        return None
    conds = [t.busy(d, is_rescue) for d in range(t.n_devices)]
    if not any(conds):
        return None
    rescue_ns = sum(hi - lo for spans in conds for lo, hi in spans)
    return 100.0 * rescue_ns / 1e9 / t.n_devices / t.busy_s
