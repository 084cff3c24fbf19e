"""Completion-to-completion and block-median arithmetic on synthetic
retire times, a disturbed block, and the drained-backlog warm-up."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import window  # noqa: E402

FPU = 32 * 100 * 4.0


def steady(n, period=0.5, start=10.0):
    return [start + i * period for i in range(n)]


def test_window_rate_is_completion_to_completion():
    retires = steady(41)                     # 40 updates in 20 s
    assert window.window_rate(retires, FPU) == pytest.approx(
        40 * FPU / 20.0)
    # Whole updates inside fixed wall-clock edges would have lost up to
    # one update in forty; here the edges ARE retires.
    assert window.window_rate(retires[:2], FPU) == pytest.approx(FPU / 0.5)
    assert window.window_rate(retires[:1], FPU) is None


def test_block_rates_cut_consecutive_blocks_of_eight():
    retires = steady(8 * 5 + 1 + 3)          # 5 whole blocks and a tail
    rates = window.block_rates(retires, FPU)
    assert len(rates) == 5
    assert all(r == pytest.approx(FPU / 0.5) for r in rates)
    assert window.block_median_rate(retires, FPU) == pytest.approx(
        window.window_rate(retires, FPU))


def test_one_disturbed_block_moves_the_window_rate_not_the_median():
    retires = steady(8 * 12 + 1)
    # A 3 s pause (a neighbour's burst, a GC) inside block 5.
    disturbed = [t + (3.0 if i > 43 else 0.0) for i, t in enumerate(retires)]
    clean = FPU / 0.5
    assert window.block_median_rate(disturbed, FPU) == pytest.approx(clean)
    whole = window.window_rate(disturbed, FPU)
    assert whole < 0.95 * clean              # the plain rate lost 6%
    # ...and the stall stays visible beside the median.
    assert window.percentile(window.intervals_ms(disturbed), 100) \
        == pytest.approx(3500.0)


def test_periodic_pauses_show_in_the_interval_tail():
    retires, t = [], 0.0
    for i in range(100):
        t += 0.5 + (0.4 if i % 8 == 0 else 0.0)
        retires.append(t)
    assert window.percentile(window.intervals_ms(retires), 95) \
        == pytest.approx(900.0)
    # every block holds one pause, so the median moves too
    assert window.block_median_rate(retires, FPU) < FPU / 0.5


def test_backlog_drained_needs_real_waits_on_half_of_the_last_eight():
    ready, real = 20e-6, 0.3
    assert not window.backlog_drained([real] * 7)
    assert window.backlog_drained([ready] * 5 + [real] * 8)
    # two actor groups in step: batches arrive in pairs, waits alternate
    assert window.backlog_drained([ready] * 5 + [real, ready] * 4)
    assert not window.backlog_drained([ready] * 5 + [real, ready] * 3)
    assert not window.backlog_drained([real] * 3 + [ready] * 5)


def test_clock_discards_the_backlog_prefix_then_measures():
    clock = window.WindowClock(seconds=10.0, min_warmup=3, needs_drain=True)
    t, statuses = 0.0, []
    # five updates fed from the queue that filled during the compile
    for _ in range(5):
        t += 0.16
        statuses.append(clock.on_retire(t, 20e-6))
    # then the loop's own pace: the learner waits for every batch
    for _ in range(40):
        t += 0.46
        statuses.append(clock.on_retire(t, 0.3))
    assert statuses[:8] == ["warmup"] * 8     # 5 backlog + 3 real waits
    assert statuses[8] == "opened"            # the fourth real wait
    assert clock.discarded == 8 and clock.drained
    assert statuses[-1] == "closed"
    span = clock.retires[-1] - clock.retires[0]
    assert span < 10.0 <= span + 0.46
    # only the steady pace is inside the window
    assert window.window_rate(clock.retires, FPU) == pytest.approx(
        FPU / 0.46)


def test_clock_opens_anyway_when_the_learner_never_waits():
    clock = window.WindowClock(seconds=1.0, min_warmup=3, needs_drain=True,
                               max_warmup=10)
    statuses = [clock.on_retire(0.1 * i, 20e-6) for i in range(12)]
    assert statuses[10] == "opened" and not clock.drained


def test_clock_gate_holds_the_window_shut():
    clock = window.WindowClock(seconds=1.0, min_warmup=2, needs_drain=False)
    clock.gate = lambda: False
    assert [clock.on_retire(0.1 * i) for i in range(5)] == ["warmup"] * 5
    clock.gate = lambda: True
    assert clock.on_retire(0.6) == "opened"


def test_spread_is_the_drivers():
    values = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    import statistics
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert window.spread(values) == pytest.approx(
        (q3 - q1) / statistics.median(values))
