"""Deliverable guards over the committed dry-run artifacts: every
(arch x applicable shape) cell must have compiled on BOTH production
meshes (36 + 36), with roofline-complete records.  Skips cleanly if the
artifact directory has not been generated yet."""
import json
from pathlib import Path

import pytest

from repro.configs import ARCHS, applicable_shapes, get_config

ART = Path(__file__).resolve().parents[1] / "experiments" / "dryrun"


def _cells():
    for arch in sorted(ARCHS):
        cfg = get_config(arch)
        for shape in applicable_shapes(cfg):
            yield arch, shape.name


@pytest.mark.parametrize("mesh", ["pod1", "pod2"])
def test_all_cells_compiled(mesh):
    if not ART.exists():
        pytest.skip("dry-run artifacts not generated")
    missing = []
    for arch, shape in _cells():
        p = ART / f"{arch}__{shape}__{mesh}.json"
        if not p.exists():
            missing.append(p.name)
            continue
        rec = json.loads(p.read_text())
        assert rec.get("compile_s", 0) > 0, p.name
        assert "corrected" in rec, p.name
    assert not missing, missing


def test_multi_pod_scales_per_device_flops():
    """The pod axis must actually shard work: per-device train flops on
    2x16x16 should be ~half of 16x16 (batch splits over pods)."""
    if not ART.exists():
        pytest.skip("dry-run artifacts not generated")
    p1 = ART / "olmo-1b__train_4k__pod1.json"
    p2 = ART / "olmo-1b__train_4k__pod2.json"
    if not (p1.exists() and p2.exists()):
        pytest.skip("olmo artifacts missing")
    f1 = json.loads(p1.read_text())["corrected"]["flops"]
    f2 = json.loads(p2.read_text())["corrected"]["flops"]
    assert 0.4 < f2 / f1 < 0.75, (f1, f2)
