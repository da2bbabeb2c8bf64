"""Estimator arithmetic on recorded samples (``data/samples.json``: lines
the benchmark printed in chip runs of this PR, and hand-made stamps)."""

import json
import os
import statistics

import pytest

from benchmark.harness import estimators as est

with open(os.path.join(os.path.dirname(__file__), "data",
                       "samples.json")) as f:
    SAMPLES = json.load(f)


def test_slice_rates_split_a_step_at_the_boundary():
    # 4 steps of 10 bytes done at 0.5, 1.5, 2.5, 3.5 s: every whole
    # slice holds one step's worth, whatever the phase
    done = [(100.5 + k, 10) for k in range(4)]
    assert est.slice_rates(done, 100.0, 3) == pytest.approx([15, 10, 10])
    # the first step accrues from t0: 10 bytes over 0.5 s, all in slice 0
    assert sum(est.slice_rates(done, 100.0, 4)) == pytest.approx(40)


def test_slice_rates_keep_every_byte_inside_whole_slices():
    done = [(t, nb) for t, nb in SAMPLES["done_stamps"]]
    t0 = SAMPLES["done_t0"]
    rates = est.slice_rates(done, t0, 3)
    inside = sum(nb for t, nb in done if t - t0 <= 3)
    assert len(rates) == 3
    assert inside <= sum(rates) <= inside + max(nb for _t, nb in done)
    assert est.total_rate(done, t0, done[-1][0]) == pytest.approx(
        sum(nb for _t, nb in done) / (done[-1][0] - t0))


def test_slice_median_ignores_one_stalled_slice_and_total_does_not():
    slices = SAMPLES["slice_gbps_with_stall"]
    assert statistics.median(slices) == pytest.approx(0.6, rel=0.02)
    assert sum(slices) / len(slices) < 0.58


def test_write_run_rates_and_their_median():
    runs = [(nb, w) for nb, w in SAMPLES["write_runs"]]
    rates = est.run_rates(runs)
    assert len(rates) == 8
    assert statistics.median(rates) == pytest.approx(
        sorted(rates)[3] / 2 + sorted(rates)[4] / 2)
    total = sum(nb for nb, _w in runs) / sum(w for _nb, w in runs)
    assert min(rates) <= total <= max(rates)


@pytest.mark.parametrize("name,down", [
    ("cold_steady", False), ("cold_one_slow_first", False),
    ("cold_warming", True), ("cold_too_few", False)])
def test_cold_start_trend(name, down):
    assert est.trend_down(SAMPLES[name]) is down


def test_percentile_is_nearest_rank():
    assert est.percentile(list(range(1, 101)), 95) == 95
    assert est.percentile([3.0], 95) == 3.0
    assert est.percentile([1, 2, 3, 4], 50) == 2
