"""Roofline share of the selective scan (``ops/selective_scan.py``): the
least time the scan's work needs over the device time of the scopes that do
it. The yardstick is defined on the work (``adapters/phi4flash.py``), not on
the implementation, so a later kernel is read against the same numbers.
``delta_rule_roofline``'s way, for another recurrence and another adapter:

``form: "chunk"``: per prefilled token. Spent: ``scope_ms_per_count`` on the
metric's scopes (``ssm_scan``) inside ``jit_prefill_chunk`` over the
``tokens`` of ``engine.prefill_dispatch``. Least: a token's work in one scan
layer (``ssm_token_work``: the recurrence's FLOPs at the bfloat16 peak
against the bytes of ``x``, ``dt``, ``z``, ``B``, ``C`` in and ``y`` out once
at the HBM bandwidth, whichever takes longer) times the scan layers. A
padded row is work spent and not work needed. The scan is exponentials and
multiply-adds on the vector units, which the bfloat16 peak (the matrix
units') does not describe, so the share reads low where those bind: it says
how far the scan is from costing no more than its bytes.

``form: "step"``: per decode step. Spent: ``scope_ms_per_count`` on the
scopes (``ssm_scan`` and ``ssm_state``: the state's bytes may be booked
under either) inside the decode programs over the ``steps`` of
``engine.decode_dispatch``. Least: the (slot, scan layer) pairs a step
updated, each a state read once and written once (``ssm_step_bytes``) at the
HBM bandwidth. The pairs a step are the growth of ``ssm_state_updates`` over
that of ``decode_steps`` over the whole measured window (``counter_ratio``),
not between the polls that bracket the traced span (``delta_rule_roofline``
says why: the counts arrive one burst late). A state walked for a slot that
does not decode is time spent and not work needed.

None where the trace has none of the scopes or ``stats()`` lacks the
counter: a program without them (the parent commit) leaves the metric out.
"""

from rtbench.readers import adapter_of, counter_ratio, scope_ms_per_count

ADAPTER_NEEDS = ("ssm_token_work", "ssm_step_bytes", "ssm_lines")


def read(obs, params):
    spent_ms = scope_ms_per_count.read(obs, params)
    if not spent_ms:
        return None
    config, adapter = obs["cell"]["config"], adapter_of(obs)
    peaks = obs["peaks"]
    if params["form"] == "chunk":
        work = adapter.ssm_token_work(config)
        least = adapter.ssm_lines(config) * max(
            work["flops"] / peaks["bf16_flops_per_s"],
            work["bytes"] / peaks["hbm_bytes_per_s"])
    else:
        pairs = counter_ratio.read(obs, {"num": "ssm_state_updates",
                                         "den": "decode_steps"})
        if not pairs:
            return None
        least = adapter.ssm_step_bytes(config, pairs) \
            / peaks["hbm_bytes_per_s"]
    return 100.0 * least * 1e3 / spent_ms
