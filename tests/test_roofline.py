"""Roofline term arithmetic against the chip's published peaks."""

import pytest

from repro.launch import roofline as rf


def test_roofline_terms_math():
    t = rf.roofline_terms(flops=197e12 * 256, bytes_accessed=819e9 * 256,
                          coll_bytes=50e9 * 256, chips=256,
                          device_kind="TPU v5 lite")
    assert abs(t["compute_s"] - 1.0) < 1e-9
    assert abs(t["memory_s"] - 1.0) < 1e-9
    assert abs(t["collective_s"] - 1.0) < 1e-9
    assert rf.dominant({"compute_s": 3, "memory_s": 2, "collective_s": 1}) \
        == "compute_s"
    with pytest.raises(ValueError, match="no published peaks"):
        rf.roofline_terms(1.0, 1.0, 0, chips=1, device_kind="cpu")
