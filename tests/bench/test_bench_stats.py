"""Metric arithmetic: due-time tails, censoring, gaps per request, and the
peaks table."""
from __future__ import annotations

import dataclasses

import pytest

from bench.harness import peaks, stats


@dataclasses.dataclass
class R:
    due: float
    tokens: list
    service_t: float | None = None

    @property
    def first_token(self):
        return self.tokens[0] if self.tokens else None


def test_bench_tails_count_from_due_time_and_censor_at_close():
    reqs = [R(due=9.0, tokens=[10.5]),          # due before the window
            R(due=10.0, tokens=[10.2, 10.3]),   # 0.2
            R(due=11.0, tokens=[12.0]),         # 1.0
            R(due=12.0, tokens=[]),             # never served: 20 - 12
            R(due=13.0, tokens=[25.0]),         # served after close: 7
            R(due=20.0, tokens=[20.1])]         # due at the close: outside
    waits = stats.censored_waits(reqs, 10.0, 20.0, "first_token")
    assert waits == pytest.approx([0.2, 1.0, 8.0, 7.0])
    assert stats.percentile(waits, 50) == pytest.approx(4.0)
    assert stats.percentile([], 90) is None


def test_bench_queue_wait_censors_unserved():
    reqs = [R(due=1.0, tokens=[], service_t=1.5),
            R(due=2.0, tokens=[], service_t=None)]
    assert stats.censored_waits(reqs, 0.0, 4.0, "service_t") == \
        pytest.approx([0.5, 2.0])


def test_bench_gaps_are_per_request_and_inside_the_window():
    a = R(due=0.0, tokens=[0.5, 1.0, 1.5, 3.0, 7.0])
    b = R(due=0.0, tokens=[1.2, 1.4])
    gaps = stats.gaps_in([a, b], 1.0, 5.0)
    # a: 1.0->1.5, 1.5->3.0 (0.5->1.0 starts before, 3.0->7.0 ends after);
    # b: 1.2->1.4; never a gap across requests
    assert sorted(gaps) == pytest.approx([0.2, 0.5, 1.5])
    assert stats.tokens_in([a, b], 1.0, 5.0) == 4    # 1.5, 3.0, 1.2, 1.4


def test_bench_percentile_interpolates():
    assert stats.percentile([1, 2, 3, 4], 50) == pytest.approx(2.5)
    assert stats.percentile(list(range(101)), 95) == pytest.approx(95.0)


def test_bench_peaks_known_and_unknown():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
