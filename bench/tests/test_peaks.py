from types import SimpleNamespace

import pytest

import peaks


def dev(platform="tpu", kind="TPU v5 lite"):
    return SimpleNamespace(platform=platform, device_kind=kind)


def test_known_kind_gives_its_peaks():
    p = peaks.require_chip([dev()], 1)
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


def test_non_tpu_platform_is_refused_by_name():
    with pytest.raises(SystemExit, match="'cpu'"):
        peaks.require_chip([dev("cpu", "cpu")], 1)


def test_unknown_kind_is_an_error():
    with pytest.raises(SystemExit, match="TPU v99"):
        peaks.require_chip([dev(kind="TPU v99")], 1)
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")


def test_too_few_chips_is_refused():
    with pytest.raises(SystemExit, match="needs 4 chips"):
        peaks.require_chip([dev()], 4)
