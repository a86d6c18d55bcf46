"""Percentile, rate and spread arithmetic, and the operation counts."""
import math

import numpy as np
import pytest

from perfbench.harness import flops, stats


@pytest.mark.parametrize("q", [0, 50, 90, 95, 99, 100])
def test_percentile_matches_numpy_linear(q):
    xs = np.random.default_rng(q).exponential(40.0, size=1001)
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q),
                                                    rel=1e-12)


def test_percentile_of_few_values_interpolates():
    assert stats.percentile([10.0, 20.0], 95) == pytest.approx(19.5)
    assert stats.percentile([3.0], 95) == 3.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_p95_is_over_all_requests_not_chunk_medians():
    # 5% of requests stalled: the tail sees them, a median of chunk p95s
    # would not
    lat = [10.0] * 950 + [500.0] * 50
    assert stats.percentile(lat, 95) == pytest.approx(10.0 + 490.0 * 0.05)
    assert stats.percentile(lat, 96) == 500.0


def test_rate_counts_per_window_second():
    assert stats.rate(450, 45.0) == 10.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_model_flops_of_a_two_layer_gcn():
    # N active users, nnz of A + I, widths 500-16-3
    got = flops.model_request(300, 3 * 300, [500, 16, 3])
    want = 2 * 300 * 500 * 16 + 2 * 900 * 16 + 2 * 300 * 16 * 3 \
        + 2 * 900 * 3
    assert got == want


def test_forward_call_counts_dense_and_gather_aggregates():
    dense, dense_bytes = flops.forward_call("dense", 2, 1, 100, 120, 9,
                                            [8, 4], False)
    assert dense == 2 * 2 * 100 * 8 * 4 + 2 * 2 * 100 * 120 * 4
    fused, _ = flops.forward_call("fused", 2, 1, 100, 120, 9, [8, 4], False)
    assert fused == 2 * 2 * 100 * 8 * 4 + 2 * 2 * 100 * 10 * 8
    _, cross_bytes = flops.forward_call("dense", 2, 1, 100, 120, 9,
                                        [8, 4], True)
    assert cross_bytes - dense_bytes == 100 * 120 * 4 + (200 + 120) * 4


def test_roofline_names_its_bound():
    peak = {"bf16_flop_per_s": 100.0, "hbm_byte_per_s": 10.0}
    assert flops.roofline_seconds(1000.0, 10.0, peak) == (10.0, "compute")
    assert flops.roofline_seconds(10.0, 1000.0, peak) == (100.0, "memory")
    assert math.isclose(flops.roofline_seconds(0.0, 0.0, peak)[0], 0.0)
