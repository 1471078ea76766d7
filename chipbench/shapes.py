"""Shapes of a device op, read from the HLO text the profiler names it by:
``%name = <result type> custom-call(<type> %a, <type> %b, ...), ...``."""
from __future__ import annotations

import re

_ARRAY = re.compile(r"\b(bf16|f16|f32|f64|s8|u8|s32|u32|pred|s64|u64)"
                    r"\[([0-9,]*)\]")


def _dims(text: str):
    return tuple(int(x) for x in text.split(",") if x)


def result_and_operands(name: str):
    """(result shape, [operand shapes]) of an op's HLO text, each a
    (dtype, dims) pair; None where the text holds no array."""
    if " = " not in name:
        return None, []
    rhs = name.split(" = ", 1)[1]
    m = _ARRAY.search(rhs)
    if m is None:
        return None, []
    result = (m.group(1), _dims(m.group(2)))
    # operands: the arrays between the op's opening parenthesis and the
    # first "), " that closes it
    head = rhs[m.end():]
    p = head.find("(")
    if p < 0:
        return result, []
    body = head[p + 1:]
    end = body.find("), ")
    body = body if end < 0 else body[:end]
    ops = [(a.group(1), _dims(a.group(2))) for a in _ARRAY.finditer(body)]
    return result, ops


_HEAD = re.compile(r"(%\S+) = (\w+\[[0-9,]*\])\S*\s+([\w-]+)")
_TUPLE = re.compile(r"(%\S+) = \(.*?\}\)\s+([a-z][\w-]*)\(")


def short(name: str, width: int = 120) -> str:
    """``%name = type[dims] opcode`` of an op's HLO text, for the
    breakdown."""
    m = _HEAD.match(name)
    if m:
        return f"{m.group(1)} = {m.group(2)} {m.group(3)}"[:width]
    m = _TUPLE.match(name)
    if m:
        return f"{m.group(1)} = (tuple) {m.group(2)}"[:width]
    return name[:width]


def dbb_gemm_mkn(name: str, block: int, nnz: int):
    """(M, K, N) of a DBB GEMM custom call, read from its operands: an
    activation [M, K], a value plane [K nnz / block, N] and a mask plane
    [K / block, N], with a result [M, N]. None for any other op."""
    if "custom-call(" not in name:
        return None
    result, ops = result_and_operands(name)
    if result is None or len(ops) < 3 or len(result[1]) != 2:
        return None
    (_, x), (_, v), (_, mk) = ops[:3]
    if len(x) != 2 or len(v) != 2 or len(mk) != 2:
        return None
    m, k = x
    n = result[1][1]
    if (v[0] * block != k * nnz or mk[0] * block != k or v[1] != n
            or mk[1] != n or result[1][0] != m):
        return None
    return m, k, n


def paged_decode(name: str):
    """(batch rows, table width) of a paged decode attention custom call:
    its operands open with the block table s32[B, n_log] and the lengths
    and starts s32[B]. None for any other op."""
    if "custom-call(" not in name:
        return None
    _, ops = result_and_operands(name)
    if len(ops) < 3:
        return None
    (t0, d0), (t1, d1), (t2, d2) = ops[:3]
    if (t0, t1, t2) != ("s32", "s32", "s32") or len(d0) != 2 \
            or d1 != (d0[0],) or d2 != (d0[0],):
        return None
    return d0
