"""Percentile and whole-window arithmetic on hand-made event lists."""

import pytest

from benchmark.lib.stats import Record, percentile, window_metrics


def rec(index, t_submit, events, done=None, error=None, out=None):
    r = Record(index=index, prompt_tokens=130, output_tokens=out or sum(n for _, n in events),
               t_due=t_submit, t_submit=t_submit)
    r.events = list(events)
    r.tokens = [7] * sum(n for _, n in events)
    r.t_done = done
    r.error = error
    return r


@pytest.mark.parametrize("values,q,want", [
    ([1.0], 95, 1.0),
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4, 5], 100, 5.0),
    ([0, 10], 95, 9.5),
    (list(range(101)), 95, 95.0),
])
def test_percentile(values, q, want):
    assert percentile(values, q) == pytest.approx(want)


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        percentile([], 50)


def steady(stall=0.0):
    """Two requests, a token group every 0.2 s from t=10; ``stall`` delays
    everything after t=11."""
    out = []
    for i in range(2):
        events, t = [], 10.0 + 0.05 * i
        events.append((t, 1))
        for _ in range(20):
            t += 0.2
            events.append((t + (stall if t > 11.0 else 0.0), 16))
        out.append(rec(i, 9.5, events, done=events[-1][0]))
    return out


def test_window_counts_all_tokens_over_all_seconds():
    w = window_metrics(steady(), 10.0, 14.5)
    assert w["tokens_per_s"]["value"] == pytest.approx(2 * (1 + 20 * 16) / 4.5)
    assert len(w["ttft_ms"]["values"]) == 2
    assert w["ttft_ms"]["values"][0] == pytest.approx(500.0)
    assert len(w["stream_gap_ms"]["values"]) == 40
    assert all(g == pytest.approx(200.0) for g in w["stream_gap_ms"]["values"])


def test_a_stall_inside_the_window_moves_rate_and_gap_tail():
    base = window_metrics(steady(), 10.0, 14.0)
    hit = window_metrics(steady(stall=1.0), 10.0, 14.0)
    assert hit["tokens_per_s"]["value"] < 0.8 * base["tokens_per_s"]["value"]
    assert percentile(hit["stream_gap_ms"]["values"], 95) > percentile(base["stream_gap_ms"]["values"], 95) + 500


def test_events_outside_the_window_do_not_count():
    w = window_metrics(steady(), 12.0, 13.0)
    assert w["ttft_ms"]["values"] == []
    assert w["tokens_per_s"]["value"] == pytest.approx(2 * 5 * 16 / 1.0)
    assert w["attempted"] == 0


def test_failed_requests_are_counted_against_attempts():
    records = steady() + [rec(9, 10.5, [], done=10.6, error="RuntimeError: refused", out=5)]
    for r in records[:2]:
        r.t_due = 10.1
    w = window_metrics(records, 10.0, 14.5)
    assert w["attempted"] == 3 and w["failed"] == 1


def test_open_loop_ttft_counts_from_when_it_was_due():
    r = rec(0, 10.4, [(10.9, 1)], done=10.9)
    r.t_due = 10.0  # the generator ran 0.4 s late: the wait still counts
    w = window_metrics([r], 10.0, 12.0)
    assert w["ttft_ms"]["values"] == [pytest.approx(900.0)]
