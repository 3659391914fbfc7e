"""Model FLOP/s utilization of training: FLOPs a token needs forward and
backward from the shapes (recomputation not counted; experts: those a
token is routed to) x tokens/s/chip over the chip's bf16 peak."""

from rtbench.readers import adapter_of

ADAPTER_NEEDS = ("train_flops_per_token", "depth")


def read(obs, params):
    if obs.get("kind") != "train":
        return None
    cell, adapter = obs["cell"], adapter_of(obs)
    flops = adapter.train_flops_per_token(
        cell["config"], adapter.depth(cell["config"], cell["traffic"]["use"]),
        cell["traffic"]["seq_len"])
    return 100.0 * flops * obs["tok_s_chip"] / obs["peaks"]["bf16_flops_per_s"]
