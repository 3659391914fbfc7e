"""Roofline share of the gated delta rule (``ops/gated_delta.py``): the
least time the rule's work needs over the device time of the scopes that do
it. The yardstick is defined on the work (``adapters/qwen3_next.py``), not
on the implementation, so a later kernel is read against the same numbers.

Both sides are taken per unit the program counts itself, which needs no
common clock (``decode_attention_roofline``'s way):

``form: "chunk"``: per prefilled token. Spent: ``scope_ms_per_count`` on the
metric's scopes (``delta_rule``) inside ``jit_prefill_chunk`` over the
``tokens`` of ``engine.prefill_dispatch``. Least: a token's work in one
linear layer (``delta_rule_token_work``: the recurrence's FLOPs at the
bfloat16 peak against the bytes of ``q``, ``k``, ``v``, ``g``, ``beta`` in
and ``o`` out once at the HBM bandwidth, whichever takes longer) times the
linear layers. A padded row is work spent and not work needed.

``form: "step"``: per decode step. Spent: ``scope_ms_per_count`` on the
scopes (``delta_rule`` and ``linear_state``: the state's bytes may be booked
under either) inside the decode programs over the ``steps`` of
``engine.decode_dispatch``. Least: the (slot, linear layer) pairs a step
updated, each a state read once and written once (``linear_step_bytes``) at
the HBM bandwidth. A state walked for a slot that does not decode is time
spent and not work needed, so the share follows how many of the slots
decode. The pairs a step are the growth of ``linear_state_updates`` over
that of ``decode_steps`` over the whole measured window (``counter_ratio``:
some 1,300 steps), not between the polls that bracket the traced span: the
engine adds a program's counts when it reads the program's result, one
burst after it counted the burst's steps at dispatch, and over the span's
hundred steps that lag and the slots that happened to decode in those 4 s
moved the quotient from 107 to 195 pairs a step (where 192 exist) between
three runs whose time a step was the same to four digits; over the window
the lag is one burst in 160 and the same runs read 159 to 169 (my chip
runs, PR 48). The time a step does not depend on how many slots decode
(every slot's state is walked), so the window's pairs and the span's time
make one quotient.

None where the trace has none of the scopes or ``stats()`` lacks the
counter: a program without them (the parent commit) leaves the metric out.
"""

from rtbench.readers import adapter_of, counter_ratio, scope_ms_per_count

ADAPTER_NEEDS = ("delta_rule_token_work", "linear_step_bytes",
                 "linear_lines")


def read(obs, params):
    spent_ms = scope_ms_per_count.read(obs, params)
    if not spent_ms:
        return None
    config, adapter = obs["cell"]["config"], adapter_of(obs)
    peaks = obs["peaks"]
    if params["form"] == "chunk":
        work = adapter.delta_rule_token_work(config)
        least = adapter.linear_lines(config) * max(
            work["flops"] / peaks["bf16_flops_per_s"],
            work["bytes"] / peaks["hbm_bytes_per_s"])
    else:
        pairs = counter_ratio.read(obs, {"num": "linear_state_updates",
                                         "den": "decode_steps"})
        if not pairs:
            return None
        least = adapter.linear_step_bytes(config, pairs) \
            / peaks["hbm_bytes_per_s"]
    return 100.0 * least * 1e3 / spent_ms
