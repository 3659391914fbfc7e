"""Roofline share of a kernel of the learned sparse attention
(``ops/sparse_attention.py``) inside the decode programs, per decode step:
the least time the step's work needs over the device time the kernel took.
The yardstick is defined on the mathematics (``adapters/keye.py``), not on
the implementation: a scored position is one index key read once and
``2 x heads x head_dim`` multiply-adds (``index_scores_work``), a selected
position one key and one value in each KV head read once and the products
of every query head (``sparse_attention_work``). A kernel that reads a
whole line where 2,048 positions were chosen does more than this and its
share says so; a later kernel is read by the same numbers.

Spent: ``kernel_ms_per_count`` on ``params["kernel"]`` inside the decode
programs over the ``steps`` of ``engine.decode_dispatch``. Least: the
positions a step scored or selected, summed over its rows and layers (the
growth of ``params["counter"]``, a decode step's rows alone, over that of
``decode_steps`` over the whole measured window: ``counter_ratio``, and
``delta_rule_roofline`` says why not between the polls that bracket the
traced span), through ``params["work"]``, the adapter's function: FLOPs at
the bfloat16 peak against bytes at the HBM bandwidth, whichever takes
longer.

None where the trace has no event of the kernel or ``stats()`` lacks the
counter: a program without them (the parent commit) leaves the metric out.
"""

from rtbench.readers import adapter_of, counter_ratio, kernel_ms_per_count

ADAPTER_NEEDS = ("index_scores_work", "sparse_attention_work")


def read(obs, params):
    spent_ms = kernel_ms_per_count.read(obs, params)
    if not spent_ms:
        return None
    a_step = counter_ratio.read(obs, {"num": params["counter"],
                                      "den": "decode_steps"})
    if not a_step:
        return None
    work = getattr(adapter_of(obs), params["work"])(
        obs["cell"]["config"], a_step)
    peaks = obs["peaks"]
    least = max(work["flops"] / peaks["bf16_flops_per_s"],
                work["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least * 1e3 / spent_ms
