"""Percentiles, the per-request gap between tokens and the rate, on records
made by hand; a failed and a timed-out request go to ``failed`` and never
to ``correct``."""

import pytest

from cellbench import stats


def rec(index, due, first=None, times=(), status="ok", max_tokens=None, **kw):
    times = list(times)
    return {"index": index, "due": due, "sent": due + 0.001,
            "first": times[0] if times else first, "token_times": times,
            "end": (times[-1] if times else due) + 0.01, "status": status,
            "http": 200, "n_tokens": len(times),
            "max_tokens": len(times) if max_tokens is None else max_tokens,
            "prompt_len": 10, "finish_reason": "length", "bad_tokens": 0, **kw}


def test_percentile_interpolates():
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.percentile([10], 95) == 10
    assert stats.percentile(list(range(101)), 95) == 95
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_itl_is_per_request_and_ignores_bursts():
    burst = rec(0, 0.0, times=[1.0, 1.0, 1.0, 1.3])     # three tokens at once
    assert stats.itl_ms(burst) == pytest.approx(100.0)
    assert stats.itl_ms(rec(1, 0.0, times=[1.0])) is None


def test_ttft_counts_from_due_time():
    assert stats.ttft_ms(rec(0, 2.0, times=[2.25, 2.5])) == pytest.approx(250.0)


def test_rate_counts_tokens_inside_the_window_of_any_request():
    before = rec(0, -5.0, times=[-1.0, 0.5, 1.5])        # due before the window
    inside = rec(1, 1.0, times=[2.0, 9.9, 10.0, 11.0])   # runs past its end
    out = stats.end_to_end([before, inside], 0.0, 10.0, chips=2)
    assert out["attempted"] == 1
    assert out["values"]["tok_s_chip"] == pytest.approx(4 / 10.0 / 2)


def test_failed_and_timed_out_go_to_failed_not_to_correct():
    good = rec(0, 1.0, times=[1.1, 1.2, 1.3])
    error = rec(1, 2.0, status="error", max_tokens=3, http=500)
    timeout = rec(2, 3.0, times=[3.5], status="timeout", max_tokens=3)
    short = rec(3, 4.0, times=[4.1, 4.2], max_tokens=5)   # cut by a full cache
    out = stats.end_to_end([good, error, timeout, short], 0.0, 10.0, 1)
    assert (out["attempted"], out["failed"]) == (4, 3)
    assert out["malformed"] == []            # nothing here can touch `correct`
    assert out["values"]["ttft_p50_ms"] == pytest.approx(100.0)


def test_mean_and_percentiles_of_ttft_and_itl():
    recs = [rec(i, float(i), times=[i + 0.1 * (i + 1), i + 0.1 * (i + 1) + 0.05 * (i + 1)])
            for i in range(4)]          # ttft 100..400 ms, gap 50..200 ms
    v = stats.end_to_end(recs, 0.0, 10.0, 1)["values"]
    assert v["ttft_mean_ms"] == pytest.approx(250.0)
    assert v["ttft_p50_ms"] == pytest.approx(250.0)
    assert v["ttft_p95_ms"] == pytest.approx(385.0)
    assert v["itl_p50_ms"] == pytest.approx(125.0)
    assert v["itl_p95_ms"] == pytest.approx(192.5)


@pytest.mark.parametrize("change, word", [
    ({"max_tokens": 2}, "asked for 2"),
    ({"finish_reason": "stop"}, "finish_reason"),
    ({"bad_tokens": 1}, "outside the vocabulary"),
])
def test_a_finished_stream_that_is_malformed_is_reported(change, word):
    r = {**rec(0, 1.0, times=[1.1, 1.2, 1.3]), **change}
    out = stats.end_to_end([r], 0.0, 10.0, 1)
    assert out["failed"] == 0 and word in out["malformed"][0]
