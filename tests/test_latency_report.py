"""Latency-reporting regression tests (round-4 verdict #6).

Pins the SEMANTICS of the latency/throughput numbers, not just their
signs: the ``latency_us``/``throughput`` element props (parity:
/root/reference/tests/nnstreamer_latency/unittest_latency.cc and the
property contract in tensor_filter_common.c:982-996).
"""

import pytest

from nnstreamer_tpu.utils.stats import InvokeStats

# -- InvokeStats props ---------------------------------------------------------


class TestInvokeStatsProps:
    def test_latency_unset_is_minus_one(self):
        assert InvokeStats().latency_us == -1

    def test_latency_is_mean_of_recent_window_us(self):
        st = InvokeStats(window=4)
        for s in (0.001, 0.002, 0.003):
            st.record(s)
        assert st.latency_us == pytest.approx(2000, abs=2)

    def test_latency_window_rolls(self):
        st = InvokeStats(window=2)
        for s in (0.010, 0.001, 0.003):
            st.record(s)
        # only the last two samples (1 ms, 3 ms) remain
        assert st.latency_us == pytest.approx(2000, abs=2)

    def test_counted_invokes_do_not_pollute_latency(self):
        st = InvokeStats()
        st.record(0.002)
        st.count()  # async dispatch: throughput-only
        assert st.latency_us == pytest.approx(2000, abs=2)
        assert st.total_invoke_num == 2

    def test_throughput_needs_two_invokes(self):
        st = InvokeStats()
        assert st.throughput_milli_fps == -1
        st.record(0.001)
        assert st.throughput_milli_fps == -1

    def test_throughput_is_interval_based_milli_fps(self, monkeypatch):
        import nnstreamer_tpu.utils.stats as stats_mod

        ts = iter([10.0, 10.5, 11.0])  # 2 intervals over 1 s
        monkeypatch.setattr(stats_mod.time, "monotonic", lambda: next(ts))
        st = InvokeStats()
        for _ in range(3):
            st.count()
        # (n-1)/(last-first) = 2 fps → 2000 milli-fps
        assert st.throughput_milli_fps == 2000

    def test_latency_report_threshold(self):
        st = InvokeStats()
        st.record(0.001)
        first = st.latency_to_report()
        assert first is not None and first > 0
        # unchanged latency: below threshold, no re-report
        assert st.latency_to_report() is None
