"""Lint the Pallas launch parameters a schedule carries in ``lowered``.

Independent re-statement of the TPU launch contract the kernels in
``repro.kernels`` assume (row blocks aligned to the 8-row sublane, lane
blocks a multiple of the 128-wide lane or the whole extent — the rule
the TPU's Pallas lowering enforces — all under the VMEM caps, blocks
never exceeding their tensor extents, every ragged final block paired
with an in-kernel mask record) — checked against the ``Layer`` shapes
alone, without calling ``search.lower``.  A block that
silently stopped dividing its extent, a dropped ragged/mask entry, or a
stale remainder all surface here as findings.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

from repro.core.workload import Layer

from repro.check.schedule import Finding

_SUBLANE = 8
_LANE = 128
_MAX_BLOCK_M = 256      # pixel/row blocks: fused_ibn / matmul_ln / flash
_MAX_BLOCK_F = 512      # feature/reduction blocks: fused_ibn / matmul_ln

KERNELS = ("fused_ibn", "matmul_ln", "flash_attention", "rwkv_chunk")


def _pow2_floor(v: int) -> int:
    p = 1
    while p * 2 <= v:
        p *= 2
    return p


def _check_block(key: str, param: str, block, extent: int, cap: int,
                 findings: List[Finding], *,
                 lane: bool = False) -> Optional[int]:
    """One launch block: an integer within the VMEM cap, never past the
    (padded) extent.  A ``lane`` block (the last dim of its operand
    block) is a multiple of 128 or the whole extent; a row block is a
    multiple of the 8-row sublane unless the extent itself is
    sub-sublane.  Returns the block when usable."""
    try:
        b = int(block)
    except (TypeError, ValueError):
        findings.append(Finding("lint.block_type", key,
                                f"{param} = {block!r} is not an int"))
        return None
    if b < 1:
        findings.append(Finding("lint.block_range", key,
                                f"{param} = {b} < 1"))
        return None
    if lane and b % _LANE and b != extent:
        findings.append(Finding(
            "lint.block_lane", key,
            f"{param} = {b} is neither a multiple of the {_LANE}-wide"
            f" lane nor the extent {extent}"))
    if b > cap:
        findings.append(Finding("lint.block_cap", key,
                                f"{param} = {b} exceeds the {cap} cap"))
    if b > max(1, extent):
        findings.append(Finding(
            "lint.block_extent", key,
            f"{param} = {b} exceeds its extent {extent}: the grid"
            " would launch fully-padded blocks"))
    if not lane and b % _SUBLANE and not (
            b < _SUBLANE and b == _pow2_floor(max(1, extent))):
        findings.append(Finding(
            "lint.block_sublane", key,
            f"{param} = {b} is not a multiple of the {_SUBLANE}-row"
            f" sublane and the extent {extent} allows one"))
    return b


def _check_ragged(key: str, axis: str, block: Optional[int], extent: int,
                  ragged: Dict[str, int],
                  findings: List[Finding]) -> None:
    """Every ragged final block needs its in-kernel mask record: the
    ``ragged`` entry for the axis, holding exactly ``extent % block``."""
    if not block:
        return
    want = max(1, extent) % block
    got = ragged.get(axis)
    if got is None:
        if want:
            findings.append(Finding(
                "lint.mask_missing", key,
                f"axis {axis!r}: block {block} leaves a ragged edge of"
                f" {want} but no mask/ragged record"))
        return
    if int(got) != want:
        findings.append(Finding(
            "lint.ragged_stale", key,
            f"axis {axis!r}: recorded ragged {got} != extent % block"
            f" = {want}"))


def lint_doc(doc: dict,
             layers: Sequence[Layer]) -> List[Finding]:
    """Lint every lowered kernel in an artifact document.  Tolerates
    partial docs (no ``lowered`` -> nothing to lint)."""
    findings: List[Finding] = []
    lowered = doc.get("lowered")
    if not lowered:
        return findings
    by_name = {l.name: l for l in layers}
    groups = doc.get("groups")
    for key, val in lowered.items():
        parts = key.split(" + ")
        missing = [p for p in parts if p not in by_name]
        if missing:
            findings.append(Finding("lint.unknown_layer", key,
                                    f"layers {missing} not in the chain"))
            continue
        group = None
        if groups is not None:
            group = next((g for g in groups if parts[0] in g), None)
            if group is None or any(p not in group for p in parts):
                findings.append(Finding(
                    "lint.cross_group", key,
                    "kernel spans layers from different fusion groups"))
                continue
        kernel = val.get("kernel")
        ragged = dict(val.get("ragged") or {})
        if kernel == "fused_ibn":
            if len(parts) != 2:
                findings.append(Finding("lint.arity", key,
                                        "fused_ibn needs (expand,"
                                        " project)"))
                continue
            expand = by_name[parts[0]]
            m = expand.b * expand.ox * expand.oy
            f = expand.k
            bm = _check_block(key, "block_m", val.get("block_m"), m,
                              _MAX_BLOCK_M, findings)
            bf = _check_block(key, "block_f", val.get("block_f"), f,
                              _MAX_BLOCK_F, findings, lane=True)
            _check_ragged(key, "m", bm, m, ragged, findings)
            _check_ragged(key, "f", bf, f, ragged, findings)
        elif kernel == "matmul_ln":
            if len(parts) != 2:
                findings.append(Finding("lint.arity", key,
                                        "matmul_ln needs (mac, norm)"))
                continue
            mac = by_name[parts[0]]
            m = mac.b * mac.ox * mac.oy
            red = mac.c * mac.fx * mac.fy
            bm = _check_block(key, "block_m", val.get("block_m"), m,
                              _MAX_BLOCK_M, findings)
            bk = _check_block(key, "block_k", val.get("block_k"), red,
                              _MAX_BLOCK_F, findings, lane=True)
            _check_ragged(key, "m", bm, m, ragged, findings)
            _check_ragged(key, "k", bk, red, ragged, findings)
        elif kernel == "flash_attention":
            qk = by_name[parts[0]]
            seq = qk.c
            if group is not None:
                sm = next((by_name[n] for n in group
                           if by_name[n].op == "softmax"), None)
                if sm is not None:
                    seq = sm.c
            bq = _check_block(key, "block_q", val.get("block_q"), seq,
                              _MAX_BLOCK_M, findings)
            bk = _check_block(key, "block_k", val.get("block_k"), seq,
                              _MAX_BLOCK_M, findings)
            _check_ragged(key, "q", bq, seq, ragged, findings)
            _check_ragged(key, "k", bk, seq, ragged, findings)
        elif kernel == "rwkv_chunk":
            scan = by_name[parts[0]]
            if scan.op != "scan" or scan.scan_kind != "wkv":
                # the kernel computes WKV (per-channel decay, bonus):
                # on any other recurrence it is a wrong answer
                findings.append(Finding(
                    "lint.scan_kind", key,
                    f"rwkv_chunk on a {scan.op} layer of kind"
                    f" {scan.scan_kind!r}; it computes 'wkv' only"))
                continue
            for param, want in (("bh", scan.b), ("t", scan.ox),
                                ("k", scan.c), ("v", scan.k)):
                if int(val.get(param, want)) != want:
                    findings.append(Finding(
                        "lint.scan_shape", key,
                        f"{param} = {val.get(param)} != layer"
                        f" extent {want}"))
            chunk = int(val.get("chunk", 0))
            if not 1 <= chunk <= scan.ox:
                findings.append(Finding(
                    "lint.scan_chunk", key,
                    f"chunk {chunk} outside [1, t={scan.ox}]"))
            else:
                # the scan tail is the kernel's only ragged edge; the
                # carry makes a dropped tail mask a silent wrong answer
                _check_ragged(key, "t", chunk, scan.ox, ragged,
                              findings)
        else:
            findings.append(Finding("lint.unknown_kernel", key,
                                    f"kernel {kernel!r} not one of"
                                    f" {KERNELS}"))
    return findings
