"""Lower a searched schedule onto concrete Pallas launch parameters.

The search operates on the zigzag-lite abstract machine; this bridge
maps its decisions onto the repo's real TPU kernels so the DSE result
drives actual launches:

  fused IBN group    -> kernels.ops.fused_ibn   (block_m, block_f)
  MAC + fused LN     -> kernels.ops.matmul_ln   (block_m, block_k)
  attention matmuls  -> kernels.ops.flash_attention (block_q, block_k)

Abstract tile sizes are snapped to blocks the TPU's Pallas lowering
accepts: row blocks are powers of two and multiples of the 8-row sublane
where the extent allows; lane-axis blocks (``block_f``, matmul_ln's
``block_k``) are power-of-two multiples of the 128-wide lane, or the
whole extent when it is at most one lane wide.  Blocks are clamped to
the tensor extents.  A block is NOT forced to divide its extent: imperfect
blocks are first-class — ``_snap`` reports the ragged final block
explicitly, the ``ops`` wrappers pad the operands to a block multiple,
and the kernels mask the padded region in-kernel (edge predication), so
the searched tile drives the launch even on EdgeNeXt's odd extents.
The emitted parameter dicts are directly splattable into the kernel
calls — ``tests/test_search.py`` runs them through the
kernel-vs-``ref`` correctness harness.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from repro import obs
from repro.core.workload import (MAC_OPS, MATMUL, NORM, PWCONV, SCAN,
                                 SOFTMAX, Layer)
from repro.search import tiler

# VMEM is ~16 MB/core; keep resident blocks far below it and aligned to
# the f32 (8, 128) tile granularity where the extents allow.
_SUBLANE = 8
_LANE = 128
_MAX_BLOCK_M = 256
_MAX_BLOCK_F = 512


def _pow2_floor(v: int) -> int:
    p = 1
    while p * 2 <= v:
        p *= 2
    return p


def _snap(v: int, lo: int, hi: int, extent: int) -> Tuple[int, int]:
    """Power-of-two block in [lo, hi] near v, clamped to the extent.

    Returns ``(block, n_ragged)``: ``block`` need not divide ``extent``;
    ``n_ragged = extent % block`` is the size of the ragged final block
    (0 when the tiling is perfect) so callers can no longer mistake an
    imperfect block for a dividing one.  A degenerate band (``lo > hi``)
    collapses to the upper bound — the cap always wins, the result never
    exceeds ``hi`` (or the extent).
    """
    extent = max(1, extent)
    if lo > hi:
        lo = hi
    b = _pow2_floor(max(1, max(lo, min(v, hi))))
    b = _pow2_floor(max(1, min(b, extent)))
    return b, extent % b


def _snap_lane(v: int, hi: int, extent: int) -> Tuple[int, int]:
    """Lane-axis block: a power-of-two multiple of the 128-wide lane in
    [128, hi] near v, or the whole extent when it is at most one lane
    wide.  The TPU lowering refuses any other lane block (it must be a
    multiple of 128 or equal the array's dimension), so a modelled tile
    narrower than a lane launches one lane wide."""
    extent = max(1, extent)
    if extent <= _LANE:
        return extent, 0
    return _snap(v, _LANE, hi, extent)


@dataclasses.dataclass(frozen=True)
class LoweredKernel:
    kernel: str     # "fused_ibn" | "matmul_ln" | "flash_attention" | "rwkv_chunk"
    layer_names: Tuple[str, ...]
    params: Dict[str, int]
    # per-axis ragged final-block sizes (0 = the block divides the
    # extent); the ops wrappers pad + the kernels mask these edges
    ragged: Dict[str, int] = dataclasses.field(default_factory=dict)


def lower_ibn(expand: Layer, project: Layer, *, local_buffer: int,
              tile_x: Optional[int] = None,
              tile_c: Optional[int] = None) -> LoweredKernel:
    """IBN fusion group -> fused_ibn(block_m, block_f): the searched
    (tile_x, tile_c) of the expanded intermediate become the (row, d_ff)
    VMEM block of the Pallas grid.

    The partition's tile (which already honored any full-width stats
    constraint) is authoritative when given; the tile search re-runs
    only when no tile was recorded.
    """
    F = expand.k
    n_pix = expand.b * expand.ox * expand.oy
    if tile_x is None or tile_c is None:
        ft = tiler.optimize_tile(expand, project,
                                 local_buffer=local_buffer)
        if ft is None:      # no feasible abstract tile: minimal blocks,
            #                 still snapped so a sub-sublane extent (e.g.
            #                 7 pixels) never gets a block larger than
            #                 its padded extent with ragged metadata
            #                 that contradicts the actual launch
            bm, rm = _snap(_SUBLANE, _SUBLANE, _MAX_BLOCK_M, n_pix)
            bf, rf = _snap_lane(_LANE, _LANE, F)
            return LoweredKernel("fused_ibn",
                                 (expand.name, project.name),
                                 {"block_m": bm, "block_f": bf},
                                 {"m": rm, "f": rf})
        tile_x, tile_c = ft.tile_x, ft.tile_c
    bm, rm = _snap(tile_x, _SUBLANE, _MAX_BLOCK_M, n_pix)
    bf, rf = _snap_lane(tile_c, _MAX_BLOCK_F, F)
    return LoweredKernel("fused_ibn", (expand.name, project.name),
                         {"block_m": bm, "block_f": bf},
                         {"m": rm, "f": rf})


def lower_matmul_ln(mac: Layer, norm: Layer, *, tile_x: int,
                    tile_c: int) -> LoweredKernel:
    """MAC layer with a fused trailing LayerNorm -> matmul_ln blocks.
    block_m covers the pixel tile (rows resident for the stats pass);
    block_k covers the reduction tile on the lane axis of x.  block_k
    need not divide K — the kernel zero-masks the ragged final reduction
    block in-kernel."""
    n_pix = mac.b * mac.ox * mac.oy
    red = mac.c * mac.fx * mac.fy
    bm, rm = _snap(tile_x, _SUBLANE, _MAX_BLOCK_M, n_pix)
    bk, rk = _snap_lane(tile_c, _MAX_BLOCK_F, red)
    return LoweredKernel("matmul_ln", (mac.name, norm.name),
                         {"block_m": bm, "block_k": bk},
                         {"m": rm, "k": rk})


def lower_attention(qk: Layer, *, tile_x: int,
                    seq: Optional[int] = None) -> LoweredKernel:
    """Attention score/value matmuls -> flash_attention blocks.  ``seq``
    is the softmax extent (the score-row length: N for standard
    attention, the head dim for XCA); blocks tile the online-softmax
    streaming over it."""
    if seq is None:
        seq = qk.c
    bq, rq = _snap(tile_x, _SUBLANE, _MAX_BLOCK_M, seq)
    bk, rk = _snap(tile_x, _SUBLANE, _MAX_BLOCK_M, seq)
    return LoweredKernel("flash_attention", (qk.name,),
                         {"block_q": bq, "block_k": bk},
                         {"q": rq, "k": rk})


def lower_scan(scan: Layer, tinfo: Dict[str, int]
               ) -> Optional[LoweredKernel]:
    """Chunked-recurrence layer -> rwkv_chunk(chunk), for a scan of kind
    "wkv" only: Mamba-2's scalar-decay SSD ("ssd") has its kernel
    (``kernels.ssd_chunk``, which the model calls directly), but no
    lowering rule targets it yet, so it lowers to nothing and counts as
    unlowered.  The searched chunk
    length IS the kernel's sequence block.  Unlike the GEMM kernels the
    chunk is not re-snapped here — the search already restricted itself
    to the pow2 chunk menu, and the carry makes the grid order
    non-negotiable (chunks run sequentially).  A non-dividing final
    chunk is reported via ``ragged["t"]``; the ops wrapper pads T and
    the kernel masks the padded tail in-kernel."""
    if scan.scan_kind != "wkv":
        return None
    chunk = max(1, min(int(tinfo.get("chunk") or 64), scan.ox))
    ragged = {"t": scan.ox % chunk} if scan.ox % chunk else {}
    return LoweredKernel("rwkv_chunk", (scan.name,),
                         {"chunk": chunk, "bh": scan.b, "t": scan.ox,
                          "k": scan.c, "v": scan.k},
                         ragged)


def lower_schedule(layers: Sequence[Layer], groups, tiles: Dict[str, dict],
                   *, local_buffer: int,
                   level_budgets: Optional[Dict[str, int]] = None
                   ) -> List[LoweredKernel]:
    """Emit kernel launch parameters for every lowerable construct in a
    partitioned schedule.

    ``groups`` is the partition's group list (objects with start/end and
    fused_nonlinear); ``tiles`` maps group-head layer names to tile
    summaries (only used for pixel-tile hints; missing entries fall back
    to kernel defaults).  ``level_budgets`` maps residence-level names to
    their capacities, so a group the tiler parked at a deeper level (the
    tile summary's ``level``) re-derives any missing tile against *that*
    buffer, not the innermost RF.
    """
    out: List[LoweredKernel] = []
    groups = list(groups)
    for g in groups:
        sl = layers[g.start:g.end]
        scan = next((l for l in sl if l.op == SCAN), None)
        if scan is not None:
            lk = lower_scan(scan, tiles.get(scan.name, {}))
            if lk is not None:
                out.append(lk)
            continue
        macs = [l for l in sl if l.op in MAC_OPS]
        names = {l.name for l in sl}
        head = macs[0].name if macs else None
        tinfo = tiles.get(head or "", {})
        rec_tx = tinfo.get("tile_x") or None       # partition's tile, if any
        rec_tc = tinfo.get("tile_c") or None
        tx = int(rec_tx or 64)
        tc = int(rec_tc or 128)
        buffer = (level_budgets or {}).get(tinfo.get("level"),
                                           local_buffer)
        # MAC->MAC pixel-aligned pair: score @ softmax @ value chains are
        # the flash-attention kernel; anything else is the fused-IBN one
        sm = next((l for l in sl if l.op == SOFTMAX), None)
        if len(macs) == 2 and tiler.chain_compatible(macs[0], macs[1]):
            if sm is not None:
                out.append(lower_attention(macs[0], tile_x=tx, seq=sm.c))
            else:
                out.append(lower_ibn(macs[0], macs[1],
                                     local_buffer=buffer,
                                     tile_x=rec_tx, tile_c=rec_tc))
            continue
        if len(macs) == 1:
            mac = macs[0]
            trailing_norm = next(
                (l for l in sl if l.op == NORM and l.name in
                 set(g.fused_nonlinear)), None)
            if mac.op in (PWCONV, MATMUL) and trailing_norm is not None:
                out.append(lower_matmul_ln(mac, trailing_norm,
                                           tile_x=tx, tile_c=tc))
                continue
            if mac.op == MATMUL and sm is not None:
                out.append(lower_attention(mac, tile_x=tx, seq=sm.c))
                continue
    # decision provenance: kernels emitted by type + groups with no
    # lowerable construct (each group lowers to at most one kernel)
    kinds: Dict[str, int] = {}
    for lk in out:
        kinds[lk.kernel] = kinds.get(lk.kernel, 0) + 1
    for kind, c in kinds.items():
        obs.count(f"lower.kernel.{kind}", c)
    unlowered = len(groups) - len(out)
    if unlowered > 0:
        obs.count("lower.groups_unlowered", unlowered)
    return out
