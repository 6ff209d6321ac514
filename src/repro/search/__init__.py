"""repro.search — ZigZag-style auto-scheduler for the edge accelerator.

Replaces the hand-coded heuristics (the fixed ``CONFIG_STACK``, the
OXC/CK/CFX mapping trio, the 9-candidate tile list) with design-space
exploration:

  mapper     spatial mappings + temporal loop orders per layer
             (dominance-pruned fast path, brute-force reference mode)
  partition  DP fusion partitioner over the layer chain
  tiler      budget-driven tile search for depth-first groups
  dse        Pareto sweep over HWSpec variants (sweep-wide shared memo,
             optional process-pool fan-out)
  lower      schedule -> concrete Pallas kernel launch parameters
  cache      JSON schedule artifacts + content-addressed cache
             (layer-signature keys)
  memo       unique-layer memo tables (``SearchMemo``)
  perf       phase timers + memo counters (``PerfRecorder``,
             the ``search.perf.*`` BENCH surface)
  auto       the orchestrator (``auto_schedule``; ``dedup=False`` is
             the bit-exact brute-force equivalence mode)

CLI: ``PYTHONPATH=src python -m repro.search --workload edgenext-s``.
"""
from repro.search.auto import Schedule, auto_schedule, evaluate_schedule
from repro.search.cache import (cached_search, load_schedule, save_schedule,
                                schedule_key)
from repro.search.dse import (DsePoint, edp_best, hw_variants,
                              memory_variants, pareto_front, sweep,
                              sweep_memory)

__all__ = [
    "Schedule", "auto_schedule", "evaluate_schedule", "cached_search",
    "load_schedule", "save_schedule", "schedule_key", "DsePoint",
    "edp_best", "hw_variants", "memory_variants", "pareto_front", "sweep",
    "sweep_memory", "WORKLOADS", "get_workload", "parse_workload",
    "layer_scopes",
]


def get_workload(name: str):
    """Named workload registry for the CLI / benchmarks / serve store.

    A ``-b<N>`` suffix on any registered base name is the batch-``N``
    serving shape (``core.workload.with_batch``): the historical
    ``edgenext-s-b4`` / ``mobilevit-s-b4`` / ``fastvit-s-b4`` entries
    are the ``N=4`` points of this family, and any other batch level
    (``vit-tiny-b16``, ``edgenext-s-b64``, ...) resolves the same way —
    the serve layer co-searches batch ∈ {1, 4, 16, 64} through exactly
    this naming."""
    from repro.configs.edgenext_s import CONFIG, reduced_edgenext
    from repro.core.workload import (edgenext_workload,
                                     efficientvit_workload,
                                     fastvit_workload, granite_workload,
                                     mobilevit_workload,
                                     recurrentgemma_workload,
                                     rwkv6_workload, vit_workload,
                                     with_batch)
    builders = {
        "edgenext-s": lambda: edgenext_workload(CONFIG),
        "edgenext-reduced": lambda: edgenext_workload(reduced_edgenext()),
        "vit-tiny": lambda: vit_workload(),
        "efficientvit-b0": lambda: efficientvit_workload(),
        "mobilevit-s": lambda: mobilevit_workload(),
        "fastvit-s": lambda: fastvit_workload(),
        "rwkv6": lambda: rwkv6_workload(),
        "recurrentgemma": lambda: recurrentgemma_workload(),
        "granite-h-micro": lambda: granite_workload(),
    }
    base, batch = parse_workload(name)
    if base not in builders:
        raise KeyError(f"unknown workload {name!r}; "
                       f"choose from {sorted(builders)} "
                       f"(optionally with a -b<N> batch suffix)")
    layers = builders[base]()
    return with_batch(layers, batch) if batch != 1 else layers


def parse_workload(name: str) -> tuple:
    """Split a registry name into ``(base, batch)``: a trailing
    ``-b<N>`` is the serving-batch suffix (``edgenext-s-b4`` ->
    ``("edgenext-s", 4)``), anything else is batch 1.  A name whose
    base segment itself ends in ``-b<N>`` never occurs in the registry,
    so the parse is unambiguous."""
    import re
    m = re.fullmatch(r"(.+)-b(\d+)", name)
    if m and int(m.group(2)) >= 1:
        return m.group(1), int(m.group(2))
    return name, 1


# the model module whose forward runs a base workload's chain in layer
# scopes; it may spell them apart from the chain (``chain_scope``) and
# open more (``OUTSIDE_CHAIN_SCOPES``)
FORWARDS = {"edgenext-s": "repro.models.edgenext",
            "edgenext-reduced": "repro.models.edgenext",
            "rwkv6": "repro.models.rwkv6",
            "granite-h-micro": "repro.models.mamba_hybrid"}


def layer_scopes(name: str) -> dict:
    """``{scope path: (op, ibn_role)}`` of the layer scopes that the
    forward behind a registered workload opens
    (``repro.obs.layers.layer_classes``), the names
    ``repro.obs.op_layers`` reads a compiled program's layers by."""
    import importlib
    from repro.obs.layers import layer_classes
    base, _ = parse_workload(name)
    if base not in FORWARDS:
        raise KeyError(f"no forward with layer scopes runs {name!r}; "
                       f"choose from {sorted(FORWARDS)}")
    module = importlib.import_module(FORWARDS[base])
    return layer_classes(get_workload(name),
                         getattr(module, "chain_scope", None),
                         getattr(module, "OUTSIDE_CHAIN_SCOPES", ()))


WORKLOADS = ("edgenext-s", "edgenext-s-b4", "edgenext-reduced", "vit-tiny",
             "efficientvit-b0", "mobilevit-s", "mobilevit-s-b4",
             "fastvit-s", "fastvit-s-b4", "rwkv6", "recurrentgemma",
             "granite-h-micro")
