"""Metric arithmetic on a hand-made frame log."""

import math

import pytest

from harness import metrics
from harness.loadgen import Record


def rec(due, sent, frames, tokens=None, ok=True, prompt_len=10):
    r = Record(actor=0, turn=0, tag="", prompt_len=prompt_len,
               max_tokens=8, greedy=False, check=False, due=due)
    r.sent = sent
    r.frame_t = list(frames)
    r.frame_tokens = list(tokens or [1] * len(frames))
    if ok:
        r.status, r.done_frame = 200, {"done": True}
        r.done_t = frames[-1] if frames else None
    return r


W = 10.0
LOG = [
    rec(1.0, 1.5, [2.0, 2.1, 2.3]),          # open loop, sent 0.5 s late
    rec(None, 3.0, [3.4, 3.4, 3.4, 3.9], [1, 1, 1, 1]),   # closed loop
    rec(-2.0, -2.0, [-1.0, 0.5, 9.5, 10.5]),  # ramp request: not in window
    rec(9.9, 9.9, [10.2, 10.4]),             # due inside, answers after
    rec(10.0, 10.0, [10.1]),                 # due at the end: outside
    rec(5.0, 5.0, [6.0], [3]),               # one frame of three tokens
]


def test_ttft_is_timed_from_the_due_time():
    got = sorted(metrics.ttfts(LOG, W))
    assert got == pytest.approx(sorted([1.0, 0.4, 0.3, 1.0]))


def test_a_request_with_no_token_is_infinitely_late():
    log = LOG + [rec(4.0, 4.0, [], ok=False)]
    assert math.inf in metrics.ttfts(log, W)


def test_window_edges():
    assert len(metrics.window_records(LOG, W)) == 4
    assert not metrics.in_window(LOG[2], W) and not metrics.in_window(LOG[4], W)


def test_lateness_only_of_open_loop_window_requests():
    assert sorted(metrics.lateness(LOG, W)) == pytest.approx([0.0, 0.0, 0.5])


def test_gaps_count_by_the_later_tokens_arrival():
    got = sorted(round(g, 6) for g in metrics.gaps(LOG, W))
    # 0.1, 0.2 | 0, 0, 0.5 | 1.5 (ends at 0.5), 9.0 (ends at 9.5) | 0, 0
    assert got == [0.0, 0.0, 0.0, 0.0, 0.1, 0.2, 0.5, 1.5, 9.0]


def test_tokens_in_window_whichever_request_they_belong_to():
    assert metrics.tokens_in_window(LOG, W) == 3 + 4 + 2 + 0 + 0 + 3


def test_frames_per_token_and_one_frame_streams():
    assert metrics.frames_per_token(LOG) == pytest.approx(15 / 17)
    assert metrics.one_frame_streams(LOG) == [LOG[5]]
    assert metrics.one_frame_streams(LOG[:5]) == []


@pytest.mark.parametrize("q,n,ok", [
    (0.5, 20, True), (0.5, 19, False), (0.9, 100, True), (0.9, 99, False),
    (0.95, 200, True), (0.95, 199, False)])
def test_percentile_with_too_few_samples_is_an_error(q, n, ok):
    xs = [float(i) for i in range(n)]
    if ok:
        assert metrics.percentile(xs, q) == pytest.approx(q * (n - 1))
    else:
        with pytest.raises(metrics.TooFewSamples):
            metrics.percentile(xs, q)


def test_percentile_interpolates():
    assert metrics.percentile([1.0, 2.0, 3.0, 10.0], 0.5, 0) == 2.5
    assert metrics.percentile([4.0, 1.0, 3.0, 2.0, 5.0], 0.9, 0) == 4.6
    with pytest.raises(ValueError):
        metrics.percentile([1.0], 1.0, 0)
