"""The peaks table and the work counted from shapes."""

import json

import pytest

from benchmark import work


def test_peaks_table_has_v5e_with_its_source():
    p = work.peak_for("TPU v5 lite")
    assert p["flops_per_s"] == 197e12
    assert p["bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


def test_every_peak_names_its_source():
    with open(work.PEAKS) as f:
        table = json.load(f)
    for kind, p in table.items():
        assert p["flops_per_s"] > 0 and p["bytes_per_s"] > 0, kind
        assert p["source"], kind


@pytest.mark.parametrize("kind", ["TPU v6 lite", "cpu", "", "tpu v5 lite"])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError):
        work.peak_for(kind)


@pytest.mark.parametrize("taps, want", [(9, 18), (25, 50)])
def test_ops_are_the_direct_stencils_multiply_adds(taps, want):
    assert work.stencil_ops(taps, 1, 1) == want
    assert work.stencil_ops(taps, 1000, 100) == want * 1000 * 100


def test_bytes_are_one_read_and_one_write():
    assert work.image_bytes(16384 * 16384 * 3) == 2 * 16384 * 16384 * 3


@pytest.mark.parametrize("ops, nbytes, bound", [
    (197e12, 1.0, "compute"), (1.0, 819e9, "memory")])
def test_least_time_names_its_bound(ops, nbytes, bound):
    t, which = work.least_time_s(ops, nbytes, work.peak_for("TPU v5 lite"))
    assert t == pytest.approx(1.0)
    assert which == bound
