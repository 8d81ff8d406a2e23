"""Tails are taken over every request and every gap of the window, not as
medians of chunks, and time to first token counts from when a request was
due."""

import harness
from repro.serving import Request

SERVE = harness.load_module("systems", "lm_serving")


def req(due, tokens, admitted=None):
    r = Request(uid=0, prompt=[1, 2], max_new_tokens=len(tokens),
                arrival_time=due)
    r.t_tokens = list(tokens)
    r.t_first_token = tokens[0]
    r.t_admitted = due if admitted is None else admitted
    return r


def test_percentile_is_over_every_value():
    values = [1.0] * 90 + [100.0] * 10
    chunks = [values[i:i + 10] for i in range(0, 100, 10)]
    chunk_median = sorted(harness.percentile(c, 95) for c in chunks)[5]
    assert harness.percentile(values, 95) == 100.0 and chunk_median == 1.0


def test_summary_pools_gaps_and_counts_the_window():
    # a: 4 tokens 0.1 s apart; b: one slow gap, due long before;
    # c: a pre-roll request whose last two tokens fall in the window
    a = req(0.0, [0.5, 0.6, 0.7, 0.8])
    b = req(0.2, [2.0, 3.5], admitted=1.9)
    c = req(-5.0, [-4.0, -0.05, 0.05, 0.15], admitted=-4.5)
    s = SERVE.summarize([a, b, c], start=0.0, seconds=3.0)
    assert s["tokens_in_window"] == 7          # b's 3.5 s token is outside
    assert abs(s["tok_s"] - 7 / 3.0) < 1e-12
    assert s["gaps"] == 5                      # b's gap ends outside
    assert abs(s["itl_p95_ms"] - 100.0) < 1e-6
    ttft = harness.percentile([0.5, 1.8], 95) * 1e3     # c arrived before
    assert abs(s["ttft_p95_ms"] - ttft) < 1e-9
    assert abs(s["queue_wait_p95_ms"] - harness.percentile([0, 1.7], 95)
               * 1e3) < 1e-9


def test_window_opens_late_on_its_own_clock():
    a = req(0.0, [0.2, 0.3, 0.4])
    s = SERVE.summarize([a], start=0.25, seconds=0.2)
    assert s["tokens_in_window"] == 2 and s["gaps"] == 2
    assert SERVE.records([a], 0.25)["requests"][0]["tokens"][0] == \
        0.2 - 0.25
