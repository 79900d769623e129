"""End-to-end arithmetic on synthetic round times with one stall."""

import pytest

from bench import stats


def _rounds(walls, n=800):
    out, t = [], 0.0
    for w in walls:
        out.append(stats.RoundRecord(t, t + w, n, n, w * 0.5))
        t += w + 0.001  # next batch made and sent after a return
    return out


def test_tps_counts_every_tx_over_the_whole_window():
    rounds = _rounds([0.05] * 40)
    assert stats.committed_tps(rounds, 2.0) == pytest.approx(40 * 800 / 2.0)


def test_a_stall_moves_the_p95_and_not_the_p50():
    steady = [0.040] * 95 + [0.041] * 5
    stalled = [0.040] * 90 + [0.041] * 4 + [1.5] * 6  # e.g. a compile
    p50 = stats.latency_percentile_ms(_rounds(steady), 50)
    assert stats.latency_percentile_ms(_rounds(stalled), 50) == p50 == \
        pytest.approx(40.0)
    assert stats.latency_percentile_ms(_rounds(steady), 95) == \
        pytest.approx(40.0)
    assert stats.latency_percentile_ms(_rounds(stalled), 95) == \
        pytest.approx(1500.0)
    # Fewer stalls than the 5% tail leave the p95 on a steady round.
    few = [0.040] * 96 + [1.5] * 4
    assert stats.latency_percentile_ms(_rounds(few), 95) == \
        pytest.approx(40.0)


def test_percentile_weights_rounds_by_their_transactions():
    rounds = [stats.RoundRecord(0, 0.010, 100, 100, 0.005),
              stats.RoundRecord(0, 0.020, 900, 900, 0.005)]
    assert stats.latency_percentile_ms(rounds, 50) == pytest.approx(20.0)
    assert stats.latency_percentile_ms(rounds, 10) == pytest.approx(10.0)
