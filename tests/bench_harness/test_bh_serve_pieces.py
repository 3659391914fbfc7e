"""Pieces of the serving harness that need no server."""

import pytest

from rtbench.kinds import serve_common
from rtbench.readers import serve_trace


@pytest.mark.parametrize("text,want", [
    ("<|31999|>", 31999), ("<|259|>", 259), ("A", 65), ("\n", 10),
    ("", None), ("�", None), ("ab", None), ("<|x|>", None)])
def test_token_id_reads_only_what_is_unambiguous(text, want):
    assert serve_common.token_id(text) == want


def _rec(**kw):
    base = {"abandoned": False, "error": None, "frames": 10,
            "max_tokens": 10, "finish": "length"}
    base.update(kw)
    return base


@pytest.mark.parametrize("rec,failed", [
    (_rec(), False),
    (_rec(frames=9), True),                      # fewer tokens than asked
    (_rec(frames=9, finish="stop"), False),      # the engine stopped itself
    (_rec(error="boom"), True),
    (_rec(frames=3, abandoned=True), False),     # cut off after the window
])
def test_request_failed(rec, failed):
    assert serve_common.request_failed(rec) is failed


def test_trace_joins_count_what_happened_inside_the_span():
    recs = [{"send_t": 0.0, "first_t": 1.0, "last_t": 11.0, "frames": 101,
             "prompt_tokens": 200},
            {"send_t": 4.0, "first_t": 6.0, "last_t": 7.0, "frames": 11,
             "prompt_tokens": 1000}]
    # span [5, 7]: request 0 makes 10 tokens a second; request 1 its first
    # token and its ten others
    assert serve_trace.output_tokens_in(recs, 5.0, 7.0) == \
        pytest.approx(20 + 1 + 10)
    assert serve_trace.mean_decoding(recs, 5.0, 7.0) == pytest.approx(1.5)
    # request 1 prefilled 1,000 tokens over [4, 6]: half inside [5, 7]
    assert serve_trace.prompt_tokens_prefilled(recs, 5.0, 7.0) == \
        pytest.approx(500)
    # request 0 holds 200 + 50.5 positions at t=6 (the span's middle) for
    # 2 s; request 1 holds 1,000 + 5.5 for 1 s
    assert serve_trace.mean_live_kv_tokens(recs, 5.0, 7.0) == \
        pytest.approx(((200 + 50.5) * 2 + (1000 + 5.5) * 1) / 2)
