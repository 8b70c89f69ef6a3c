"""The peaks table and the work functions."""

import pytest

from benchmark import peaks
from benchmark.work import bm25, knn


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99 imaginary")
    with pytest.raises(KeyError):
        peaks.least_seconds(1.0, 1.0, "cpu")


def test_v5e_peaks_and_least_time():
    p = peaks.peaks("TPU v5 lite")
    assert p["flops"] == 197e12 and p["bytes_per_s"] == 819e9
    # bandwidth-bound: 819 GB in one second
    assert peaks.least_seconds(1.0, 819e9, "TPU v5 lite") == 1.0
    # compute-bound: 197 TFLOP in one second
    assert peaks.least_seconds(197e12, 1.0, "TPU v5 lite") == 1.0


def test_bm25_work_counts_real_postings():
    flops, nbytes = bm25.work(postings=1000, queries=2, k=1000)
    assert flops == 8 * 1000
    assert nbytes == 12 * 1000 + 8 * 1000 * 2


def test_knn_work_reads_the_slab_once_per_launch():
    flops, nbytes = knn.work(launches=3, queries=5, n_docs=100, dims=768)
    assert flops == 2 * 5 * 100 * 768
    assert nbytes == 3 * 100 * 768 * 2


def test_program_prefixes_name_the_kernels():
    assert all(p.startswith("jit_") for p in bm25.PREFIXES + knn.PREFIXES)
