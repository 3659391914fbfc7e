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


# ------------------------------------------ the sample and its hidden tokens
def _done(index, texts, prompt_tokens=10, last_t=5.0, **kw):
    return dict({"index": index, "error": None, "abandoned": False,
                 "frames": len(texts), "max_tokens": len(texts),
                 "prompt_tokens": prompt_tokens, "last_t": last_t,
                 "texts": texts}, **kw)


@pytest.mark.parametrize("min_readable,want", [
    (None, [3, 1]),          # readable throughout, the shortest first
    (2, [3, 1, 9]),          # then one cut at its first hidden token
    (1, [3, 1, 4, 9]),       # the shorter cut one first
])
def test_pick_sample_takes_whole_answers_first_then_readable_starts(
        min_readable, want):
    recs = [_done(1, ["A", "<|300|>", "b"], prompt_tokens=20),
            _done(2, ["\ufffd", "", "c"]),              # hidden from the start
            _done(3, ["A", "<|300|>"]),
            _done(4, ["A", "\ufffd"], prompt_tokens=5),
            _done(5, ["", "ab"]),
            _done(6, ["A", "B"], last_t=50.0),          # after the window
            _done(7, ["A", "B"], error="boom"),
            _done(8, ["A"], max_tokens=2),              # stopped short
            _done(9, ["A", "<|301|>", "", "B"])]
    spec = {"requests": 9}
    if min_readable is not None:
        spec["min_readable"] = min_readable
    got = serve_common.pick_sample(recs, spec, 0.0, 10.0)
    assert [r["index"] for r, _ids in got] == want
    ids = dict((r["index"], ids) for r, ids in got)
    assert ids[3] == [65, 300]
    if 9 in ids:        # compared up to the hidden token, nothing after it
        assert ids[9] == [65, 301]
    assert [r["index"] for r, _ in serve_common.pick_sample(
        recs, dict(spec, requests=1), 0.0, 10.0)] == want[:1]


@pytest.mark.parametrize("out,want", [
    ([65, 200, 310], 0.0),       # the reference's own choice everywhere
    ([65, 200], 0.0),            # a readable start of it
    ([65, 201, 310], 1.0),       # a wrong token shows at its own position
    ([65, 200, 399], 0.1),       # a near miss by its distance
])
def test_worst_margin_is_the_furthest_a_token_lies_under_the_top(out, want):
    import numpy as np

    vocab, prompt, truth = 400, [300, 301], [65, 200, 310]

    def logits_of(seq):
        # the right continuation of the true sequence scores 1.0, and only
        # while the sequence so far is the true one; id 399 scores 0.9
        rows = np.zeros((len(seq), vocab), np.float32)
        full = prompt + truth
        for p in range(len(seq) - 1):
            if seq[:p + 1] == full[:p + 1]:
                rows[p, full[p + 1]] = 1.0
        rows[:, 399] = 0.9
        return rows

    assert serve_common.worst_margin(prompt, out, logits_of) == \
        pytest.approx(want)


def test_tpot_ms_counts_requests_that_finished_inside_the_window():
    def rec(first_t, last_t, frames=11, **kw):
        return _rec(first_t=first_t, last_t=last_t, frames=frames,
                    max_tokens=frames, **kw)

    recs = [rec(1.0, 2.0),                      # 100 ms a token
            rec(1.0, 3.0),                      # 200
            rec(1.0, 12.0),                     # ends after the window
            rec(1.0, 2.0, error="boom"),
            rec(1.0, 2.0, abandoned=True),
            rec(2.0, 2.0, frames=1)]            # one token: no gap to time
    assert serve_common.tpot_ms(recs, 0.0, 10.0) == pytest.approx(
        [100.0, 200.0])
    # all the decoding time over all the tokens: 3 s over 20 gaps
    assert serve_common.tpot_mean_ms(recs, 0.0, 10.0) == pytest.approx(150.0)
    recs.append(rec(1.0, 7.0, frames=41))       # a long answer weighs more
    assert serve_common.tpot_mean_ms(recs, 0.0, 10.0) == pytest.approx(150.0)
    assert serve_common.tpot_mean_ms(recs[:-1] + [rec(1.0, 9.0, frames=41)],
                                     0.0, 10.0) == pytest.approx(11e3 / 60)
    with pytest.raises(ValueError):
        serve_common.tpot_mean_ms(recs, 20.0, 30.0)
