"""Window arithmetic of the serving metrics on hand-made records."""
from benchmark.lib import serve_stats


def _rec(k, due, times, prompt=10):
    return {"k": k, "due": due, "sent": None if due is None else due + .001,
            "token_t": times, "tokens": [1] * len(times), "finish": None,
            "error": None, "prompt_tokens": prompt, "max_tokens": 99}


def test_gaps_are_sampled_where_they_end_inside_the_window():
    recs = [_rec(0, 5.0, [9.0, 9.5, 10.0, 10.5, 11.0]),     # began before
            _rec(1, 18.0, [19.0, 19.5, 20.0, 20.5])]        # ends after
    st = serve_stats.reduce(recs, 10.0, 20.0, 5.0)
    assert st["itl_samples"] == 3 + 1


def test_ttft_is_timed_from_when_the_request_was_due():
    recs = [_rec(0, 9.9, [10.2]),               # due before the window
            _rec(1, 10.0, [10.4, 10.5]),
            _rec(2, 19.0, [25.0]),              # first token past the limit
            _rec(3, 19.5, [])]                  # never got one
    st = serve_stats.reduce(recs, 10.0, 20.0, 5.0)
    assert st["attempted"] == 3 and st["failed"] == 2
    assert st["ttft_samples"] == 2
    assert abs(st["ttft_mean_ms"] - (400 + 6000) / 2) < 1e-6
    assert abs(st["gen_lateness_p99_ms"] - 1.0) < 1e-6


def test_live_load_is_a_time_average():
    recs = [_rec(0, None, [0.0, 1.0, 2.0], prompt=100)]
    load = serve_stats.live_load(recs, 0.0, 4.0)
    assert load["live_slots"] == 0.5
    assert load["live_tokens"] == (101 + 102) / 4.0


def test_percentile_interpolates():
    assert serve_stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert serve_stats.percentile([0, 10], 99) == 9.9
