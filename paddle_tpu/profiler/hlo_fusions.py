"""What XLA fused into each matrix product of a compiled program.

A property of the compiled program, not of a run: on the TPU a product
(``convolution`` in the optimized HLO) is emitted with whatever
elementwise chain XLA pulled into it. A chain on the OUTPUT side runs
once an element; a chain on an OPERAND side is recomputed on every pass
the product's tiling makes over that operand, and where it holds a
transcendental (``exponential`` / ``divide`` / ``log``: SiLU, softmax)
the MXU waits for the vector units. :func:`product_fusions` reads
``compiled.as_text()`` and says, for each fusion that holds a product,
which such operations sit in a producer of the product's operands, with
the compiler's own ``estimated_cycles`` for the fusion;
:func:`summary` counts them, and
``compile_observatory.record_program`` keeps the count with a program
family. :func:`grouped_products` reads the same text for the grouped
matrix products (``jax.lax.ragged_dot``, Mosaic calls ``ragged-dot...``
on the TPU) and the tile each was compiled with: the witness that
``moe/held.py::grouped_tiling`` engaged. Stdlib only, like the
observatory.
"""
from __future__ import annotations

import re

__all__ = ["parse_computations", "product_fusions", "grouped_products",
           "summary"]

#: opcodes that make an operand-side chain expensive to recompute
TRANSCENDENTALS = frozenset(("exponential", "divide", "log"))

_HEADER = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%([\w.\-]+)\s+=\s+(.*)$")
_NAME = re.compile(r"%([\w.\-]+)")
_CALLS = re.compile(r"\bcalls=%([\w.\-]+)")
_CYCLES = re.compile(r'"estimated_cycles":"(\d+)"')
_TILING = re.compile(r'ragged_dot_tiling="(\d+),(\d+),(\d+)"')
_LAYOUT = re.compile(r"\{[^{}]*\}$")


def _closing(s, start):
    """Index of the parenthesis that closes the one at ``s[start]``."""
    depth = 0
    for i in range(start, len(s)):
        if s[i] == "(":
            depth += 1
        elif s[i] == ")":
            depth -= 1
            if depth == 0:
                return i
    return len(s) - 1


def _parse_instruction(rest):
    """``<type> <opcode>(<operands>)<attributes>`` -> opcode, operand
    names, called computation, estimated cycles, result type. A tuple
    type has parentheses of its own, so it is split off by matching
    them."""
    pos = _closing(rest, 0) + 1 if rest.startswith("(") else rest.find(" ")
    result, body = rest[:pos], rest[pos:].lstrip()
    paren = body.find("(")
    if paren < 0:
        return body.strip(), [], None, None, result
    close = _closing(body, paren)
    attrs = body[close + 1:]
    calls = _CALLS.search(attrs)
    cycles = _CYCLES.search(attrs)
    return (body[:paren], _NAME.findall(body[paren + 1:close]),
            calls.group(1) if calls else None,
            int(cycles.group(1)) if cycles else None, result)


def parse_computations(text):
    """``{computation: {instruction: (opcode, operands, calls, cycles,
    result type)}}`` of an optimized HLO module's text."""
    comps, cur = {}, None
    for line in text.splitlines():
        if cur is None:
            m = _HEADER.match(line)
            if m:
                cur = comps.setdefault(m.group(1), {})
        elif line.startswith("}"):
            cur = None
        else:
            m = _INSTR.match(line)
            if m:
                cur[m.group(1)] = _parse_instruction(m.group(2))
    return comps


def _opcodes(comps, name, seen):
    """Every opcode of a computation and of the fusions nested in it."""
    if name in seen or name not in comps:
        return set()
    seen.add(name)
    found = set()
    for opcode, _, calls, _, _ in comps[name].values():
        found.add(opcode)
        if calls:
            found |= _opcodes(comps, calls, seen)
    return found


def _operand_side(comps, comp):
    """Opcodes of everything inside ``comp`` that feeds a product's
    operands, and the result types of the product's direct operands."""
    body = comps[comp]
    found, types, seen = set(), [], set()
    stack = []
    for opcode, operands, _, _, _ in body.values():
        if opcode == "convolution":
            stack += operands
            types += [body[o][4] for o in operands if o in body]
    while stack:
        name = stack.pop()
        if name in seen or name not in body:
            continue
        seen.add(name)
        opcode, operands, calls, _, _ = body[name]
        found.add(opcode)
        if calls:
            found |= _opcodes(comps, calls, set())
        stack += operands
    return found, types


def product_fusions(text):
    """One record for each fusion that holds a product: ``name``,
    ``estimated_cycles`` (None where the compiler gives none),
    ``operand_side`` (the transcendentals found in a producer of the
    product's operands, sorted), ``anywhere`` (those found in the fusion
    at all, so an output-side epilogue shows too), ``operand_types`` (the
    product's operands as the fusion hands them over), ``inputs`` (the
    result types of the arrays the fusion reads) and ``result`` (the type
    it writes)."""
    comps = parse_computations(text)
    fused = set()
    for body in comps.values():
        fused.update(c for _, _, c, _, _ in body.values() if c)
    out = []
    for cname, body in comps.items():
        if cname in fused:
            continue                      # only top-level fusion calls
        for name, (opcode, operands, calls, cycles, result) in body.items():
            if opcode != "fusion" or calls not in comps:
                continue
            if not any(o == "convolution"
                       for o, *_ in comps[calls].values()):
                continue
            side, types = _operand_side(comps, calls)
            out.append({
                "name": name, "estimated_cycles": cycles, "result": result,
                "operand_side": sorted(side & TRANSCENDENTALS),
                "anywhere": sorted(_opcodes(comps, calls, set())
                                   & TRANSCENDENTALS),
                "operand_types": types,
                "inputs": [body[o][4] for o in operands if o in body],
            })
    return out


def grouped_products(text):
    """One record for each grouped matrix product (a custom call named
    ``ragged-dot...``; the call that builds its tile lists,
    ``ragged-dot-metadata``, is not one): ``name`` (the device event's
    name), ``lhs`` / ``rhs`` / ``result`` (the rows, the stacked weights
    and what is written, as ``dtype[dims]``) and ``tiling``: the
    ``[rows, contraction, columns]`` of its ``ragged_dot_tiling``
    attribute, the compiler's own choice or the one handed to it; None
    where the call carries none."""
    types, calls = {}, []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        types[name] = rest.split(" ", 1)[0]
        if (name.startswith("ragged-dot") and "metadata" not in name
                and " custom-call(" in rest):
            _, operands, _, _, result = _parse_instruction(rest)
            tiling = _TILING.search(rest)
            calls.append((name, operands[-2:], result,
                          [int(v) for v in tiling.groups()] if tiling
                          else None))

    def shape(of):
        return _LAYOUT.sub("", of) if of else None

    return [{"name": name, "lhs": shape(types.get(ops[0])),
             "rhs": shape(types.get(ops[1])), "result": shape(result),
             "tiling": tiling}
            for name, ops, result, tiling in calls if len(ops) == 2]


def summary(text):
    """Counts over :func:`product_fusions`: fusions that hold a product,
    those with a transcendental on an operand side, and the estimated
    cycles of each group and of every operation of the program; and the
    program's :func:`grouped_products`."""
    recs = product_fusions(text)
    hot = [r for r in recs if r["operand_side"]]
    return {
        "product_fusions": len(recs),
        "operand_side_transcendental": len(hot),
        "product_cycles": sum(r["estimated_cycles"] or 0 for r in recs),
        "operand_side_cycles": sum(r["estimated_cycles"] or 0
                                   for r in hot),
        "program_cycles": sum(int(c) for c in _CYCLES.findall(text)),
        "grouped_products": grouped_products(text),
    }

