"""Which scheduler layer each op of a compiled program belongs to.

The model forwards open a ``jax.named_scope`` per layer, spelled as the
scheduler's layer name (``repro.search.get_workload``): EdgeNeXt-S's
``s1.conv0`` block scope around its ``pw1`` layer scope, RWKV-6's
``tmix`` around ``wkv`` inside the layer scan.  XLA keeps the scope path
in each instruction's ``op_name`` metadata
(``jit(served)/jit(main)/s1.conv0/pw1/dot_general``), so the optimized
HLO text of the served program says which layer every instruction the
device runs came from.

    op_layers(hlo_text, names)          {instruction name: layer name}
    layer_classes(layers, scope_of, outside)
                                        {scope path: (op, ibn_role)}

Rules: an instruction's layer is the innermost run of consecutive scope
components whose ``.``-join is one of ``names``, once the components
JAX's transforms add (``jit(f)``, a scan's ``while``/``body``/
``closed_call``, ``checkpoint``) are dropped: a layer scope inside a
``lax.scan`` in a block scope (``s1.conv0/while/body/closed_call/pw1``)
is still ``s1.conv0.pw1``.  A fusion counts to the
layer its own ``op_name`` names, which XLA copies from the op the fusion
was built around (the matmul or convolution of an output fusion), and
else to the layer of its fused computation's root instruction; by the
root alone, RWKV-6's r/k/v projection matmuls would count to the WKV
scan whose f32 cast ends their fusion.  An instruction XLA hoisted out
of a loop, which keeps no ``op_name``, counts to the one layer that
consumes it inside the loop.  An instruction with no layer
scope (a layout copy XLA inserts, a caller's reshape around the forward)
maps to nothing, and so does one whose scope was renamed away from
``names``: it shows as missing, never as some other layer.
"""
from __future__ import annotations

import dataclasses
import re
from typing import (Callable, Dict, Iterable, List, Optional, Set,
                    Tuple)

# op_name components that JAX's transforms add between scopes
_TRANSFORM = re.compile(r"while|body|cond|closed_call|core_call|checkpoint"
                        r"|remat\w*|branch_\d+|custom_\w+_call|\w+\(.*\)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REFERENCE = re.compile(r"%([\w.\-]+)")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_BODY = re.compile(r"\bbody=%?([\w.\-]+)")
_INDEX = re.compile(r"\bindex=(\d+)")
_HOIST_DEPTH = 8           # instructions followed from a hoisted one


@dataclasses.dataclass
class _Inst:
    computation: str
    opcode: str
    op_name: Optional[str]
    operands: List[str]
    rest: str


def layer_of(op_name: str, names) -> Optional[str]:
    """The layer an ``op_name`` path names, or None: the innermost run of
    consecutive path components whose ``.``-join is in ``names``, the
    transforms' components left out (the last component, the
    primitive, is never part of a layer's name)."""
    parts = [p for p in op_name.split("/")[:-1]
             if not _TRANSFORM.fullmatch(p)]
    for end in range(len(parts), 0, -1):
        for start in range(end):
            name = ".".join(parts[start:end])
            if name in names:
                return name
    return None


def _parse(hlo_text: str) -> Tuple[Dict[str, _Inst], Dict[str, str]]:
    """Instructions by name, and each computation's root."""
    insts: Dict[str, _Inst] = {}
    roots: Dict[str, str] = {}
    computation = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line) if computation is not None else None
        if m:
            root, name, rest = m.groups()
            op = _OPCODE.search(" " + rest)
            meta = _OP_NAME.search(rest)
            insts[name] = _Inst(computation, op.group(1) if op else "",
                                meta.group(1) if meta else None,
                                _REFERENCE.findall(rest), rest)
            if root:
                roots[computation] = name
            continue
        m = _COMPUTATION.match(line)
        if m:
            computation = m.group(1)
        elif line.startswith("}"):
            computation = None
    for inst in insts.values():     # keep references to instructions
        inst.operands = [o for o in inst.operands if o in insts
                         and insts[o].computation == inst.computation]
    return insts, roots


def op_layers(hlo_text: str, names: Iterable[str]) -> Dict[str, str]:
    """``{instruction name: layer name}`` for every instruction of an
    optimized HLO module's text that has a layer scope.  Instruction
    names are unique in a module, and are the names the profiler gives
    the device's ops.

    XLA hoists loop-invariant work out of a ``while`` loop (RWKV-6's
    weight casts leave the layer scan) and the hoisted instruction
    carries no ``op_name``: it counts to the one layer that consumes its
    result inside the loop, if there is one."""
    names = frozenset(names)
    insts, roots = _parse(hlo_text)

    def own(name: str) -> Optional[str]:
        meta = insts[name].op_name
        return layer_of(meta, names) if meta else None

    out: Dict[str, str] = {}
    for name, inst in insts.items():
        found = own(name)
        if found is None and inst.opcode == "fusion":
            c = _CALLS.search(inst.rest)
            root = roots.get(c.group(1)) if c else None
            found = own(root) if root else None
        if found is not None:
            out[name] = found

    users: Dict[str, List[str]] = {}
    params: Dict[str, str] = {}              # computation -> parameter
    for name, inst in insts.items():
        for o in inst.operands:
            users.setdefault(o, []).append(name)
        if inst.opcode == "parameter":
            params[inst.computation] = name

    def consumers(name: str, depth: int, inside: bool) -> Set[str]:
        """Layers that consume ``name``'s result, followed through
        instructions without a layer and into a loop's body through the
        loop's operand tuple; a layer counts only once the walk is
        inside the loop (``inside``)."""
        found: Set[str] = set()
        if depth > _HOIST_DEPTH:
            return found
        for u in users.get(name, ()):
            if u in out:
                if inside:
                    found.add(out[u])
                continue
            inst = insts[u]
            if inst.opcode == "tuple":
                index = inst.operands.index(name)
                for w in users.get(u, ()):
                    body = _BODY.search(insts[w].rest) \
                        if insts[w].opcode == "while" else None
                    param = params.get(body.group(1)) if body else None
                    for g in users.get(param, ()):
                        i = _INDEX.search(insts[g].rest)
                        if i and int(i.group(1)) == index:
                            found |= consumers(g, depth + 1, True)
            elif inside or inst.op_name is None:
                found |= consumers(u, depth + 1, inside)
        return found

    for name, inst in insts.items():
        if name not in out and inst.op_name is None:
            layers = consumers(name, 0, False)
            if len(layers) == 1:
                out[name] = layers.pop()
    return out


def layer_classes(layers: Iterable, scope_of: Optional[Callable[[str], str]]
                  = None, outside: Iterable[str] = ()
                  ) -> Dict[str, Tuple[Optional[str], Optional[str]]]:
    """``{scope path: (op, ibn_role)}`` of a chain of scheduler layers
    (``repro.search.get_workload``): each layer under its own name and
    under the scope path its forward spells it with (``scope_of``: one
    scan body serves every RWKV-6 block, so ``blk3.tmix.wkv`` runs as
    ``tmix.wkv``), and the ``outside`` scopes the forward opens around
    work the chain leaves out, with class ``(None, None)``.  The model
    module that opens the scopes declares both
    (``repro.search.layer_scopes``)."""
    out: Dict[str, Tuple[Optional[str], Optional[str]]] = {}
    for layer in layers:
        cls = (layer.op, layer.ibn_role)
        out[layer.name] = cls
        if scope_of is not None:
            out.setdefault(scope_of(layer.name), cls)
    for name in outside:
        out.setdefault(name, (None, None))
    return out
