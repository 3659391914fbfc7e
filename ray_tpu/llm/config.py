"""LLM configs.

Capability parity with the reference's LLM config surface (reference:
python/ray/llm/_internal/serve/core/configs/llm_config.py:141 LLMConfig —
model id + engine kwargs + placement; engine kwargs tensor_parallel_size
vllm_models.py:226). TPU-native: the engine is JAX; parallelism is a mesh
axis, not a worker-process count.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Union

from ray_tpu.models.deepseek import DeepseekV2Config
from ray_tpu.models.granite import GraniteConfig
from ray_tpu.models.keye import KeyeConfig
from ray_tpu.models.lfm2 import Lfm2Config
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.models.longcat import LongcatConfig
from ray_tpu.models.ling import LingConfig
from ray_tpu.models.mimo import MimoConfig
from ray_tpu.models.ouro import OuroConfig
from ray_tpu.models.phi4flash import Phi4FlashConfig
from ray_tpu.models.qwen3_next import Qwen3NextConfig
from ray_tpu.models.sdar import SdarConfig

# The served models: a model's own configuration, which is what
# llm/served.served_model knows a model by, and the module that holds its
# programs and its ``SERVED`` (imported when the configuration is first
# asked for). A new model is its two files (models/<name>.py,
# llm/<name>_serving.py) and one line here.
SERVING_MODULES = {
    LlamaConfig: "ray_tpu.llm.llama_serving",
    LongcatConfig: "ray_tpu.llm.longcat_serving",
    OuroConfig: "ray_tpu.llm.ouro_serving",
    Lfm2Config: "ray_tpu.llm.lfm2_serving",
    SdarConfig: "ray_tpu.llm.sdar_serving",
    DeepseekV2Config: "ray_tpu.llm.deepseek_serving",
    Qwen3NextConfig: "ray_tpu.llm.qwen3_next_serving",
    Phi4FlashConfig: "ray_tpu.llm.phi4flash_serving",
    MimoConfig: "ray_tpu.llm.mimo_serving",
    LingConfig: "ray_tpu.llm.ling_serving",
    GraniteConfig: "ray_tpu.llm.granite_serving",
    KeyeConfig: "ray_tpu.llm.keye_serving",
}
ModelConfig = Union[tuple(SERVING_MODULES)]


@dataclass
class SamplingParams:
    max_tokens: int = 64
    temperature: float = 0.0  # 0 → greedy
    top_p: float = 1.0
    top_k: int = 0  # 0 → disabled
    stop_token_ids: tuple[int, ...] = ()
    seed: int | None = None


@dataclass
class LLMConfig:
    model: ModelConfig | str = "tiny"  # a config or a named geometry
    tokenizer: str = "byte"            # "byte" or a HF tokenizer path
    max_num_seqs: int = 8              # continuous-batching slots
    max_seq_len: int | None = None     # default: model.max_seq_len
    dtype: str | None = None           # default: model.dtype
    tensor_parallel_size: int = 1      # tp axis size on the device mesh
    checkpoint_path: str | None = None # orbax dir; None → seeded random init
    seed: int = 0
    prefill_bucket_min: int = 16
    # Chunked prefill: long prompts prefill in chunks of this many tokens so
    # active decodes run between chunks (bounds time-per-output-token under
    # prefill load; reference shape: vLLM enable_chunked_prefill).
    prefill_chunk: int = 512
    engine_kwargs: dict[str, Any] = field(default_factory=dict)
    # Per-replica gang placement (reference: llm_config.py:181
    # placement_group_config): {"bundles": [{...}, ...], "strategy": "PACK"}.
    # Bundle 0 hosts the replica actor; the rest reserve TP/PP worker hosts.
    placement_group_config: dict | None = None
    # Speculative decoding (reference capability: vLLM speculative decoding
    # behind the llm serving stack): a small draft model proposes
    # speculative_tokens greedily; the target verifies them in ONE forward.
    # Greedy (temperature==0) requests only — output is provably identical
    # to vanilla greedy decoding regardless of draft quality.
    speculative_model: LlamaConfig | str | None = None
    speculative_tokens: int = 4
    speculative_checkpoint_path: str | None = None
    # Burst decoding: run up to this many decode+sample steps in ONE jitted
    # dispatch (lax.scan feeds each sampled token into the next step on
    # device). Amortizes the host→device dispatch + token-fetch roundtrip —
    # a large per-token cost when the model is small — across D tokens; 1
    # restores step-per-dispatch. A step reads the weights and the live
    # K/V blocks of the decoding slots once and writes one row a slot in
    # place (ops/decode_attention.py), so a burst costs D such steps and
    # nothing that grows with max_seq_len or with idle slots. The
    # burst length adapts down (powers of two) near request token budgets,
    # so only {8,4,2} shapes ever compile. Sampling inside a burst supports
    # temperature/top-p; a top-k request in the batch falls back to
    # single-step ticks.
    decode_burst: int = 8
    # Look-ahead of one: while any line decodes, the scheduler dispatches
    # the next program group (the chunks of a waiting prompt, then a burst)
    # BEFORE it reads the oldest result in flight, every burst and also
    # while a request waits or prefills, so a burst is always queued behind
    # the one that runs and the host's work (the read, emission, admission,
    # the next inputs) costs the device no gap. Tokens pass from burst to
    # burst, and from a prompt's last chunk to its first burst, on the
    # device; the host plans lengths and budgets from its own state plus the
    # steps in flight. A line that ends on a stop token is found one burst
    # late (at most one burst of wasted slot-steps). False: strictly serial,
    # a tick reads all it dispatched before the next begins. Greedy output
    # is the same either way, token for token.
    decode_pipeline: bool = True
    # Prefill chunks dispatched per admission pass of a scheduler tick (a
    # tick has two: before and after its blocking read). A bigger budget
    # brings a burst of new requests to their first tokens sooner, at the
    # cost of that many chunks of prefill compute between two decode
    # bursts (time-per-output-token under prefill load).
    prefill_chunks_per_tick: int = 4
    # Always 0. The engine has one KV layout (slot lines) and refuses any
    # other value; the field stays only because the benchmark's traffic
    # files pass it (ROADMAP D1), and goes when they drop the key.
    kv_block_size: int = 0
    # Prefix-cache publication granularity: prompt token ids are hashed in
    # chained blocks of this many tokens (serve/prefix.py); the engine
    # publishes the chain hashes of every cached prompt prefix so the serve
    # router can score replicas by matched prefix length (KV-block-aware
    # routing). 0 disables publication. Callers computing request-side
    # hashes (handle.options(prefix_hashes=...)) must use the same block
    # size over the same token ids.
    prefix_block_tokens: int = 32
    # Disaggregated prefill/decode KV hand-off transport:
    #   "store"  — the prefill server exports the prompt KV as TWO
    #              store-backed ndarrays (ray_tpu.put) and ships ObjectRefs
    #              in the payload; the decode server imports straight from
    #              the object plane (same-host: pinned read-only arena
    #              views; cross-host: cut-through transfer pulls). Zero
    #              pickle/serialize of the KV tensors on the TTFT path.
    #   "inline" — legacy: the KV ndarrays ride the handle call pickled
    #              inside the payload dict (one serialize + one deserialize
    #              copy per hop). Kept for A/B benching and as a fallback.
    pd_transfer_mode: str = "store"

    def model_config(self) -> ModelConfig:
        return _resolve_model(self.model, self.dtype)

    def draft_model_config(self) -> LlamaConfig | None:
        if self.speculative_model is None:
            return None
        return _resolve_model(self.speculative_model, self.dtype)


def _resolve_model(model: "ModelConfig | str",
                   dtype: str | None) -> ModelConfig:
    if isinstance(model, ModelConfig):
        cfg = model
    elif model == "tiny":
        from dataclasses import replace
        # vocab 512 so the byte tokenizer (256 bytes + specials) fits
        cfg = replace(LlamaConfig.tiny(), vocab_size=512)
    elif model in ("llama3-8b", "llama3_8b"):
        cfg = LlamaConfig.llama3_8b()
    elif model in ("llama3-1b", "llama3_1b"):
        cfg = LlamaConfig.llama3_1b()
    else:
        raise ValueError(f"unknown model {model!r}")
    if dtype is not None and cfg.dtype != dtype:
        from dataclasses import replace
        cfg = replace(cfg, dtype=dtype)
    return cfg
