"""ray_tpu.llm: JAX LLM inference engine + OpenAI-compatible serving.

Capability parity with the reference's ray.llm (reference: python/ray/llm/
— LLMConfig, LLMServer over vLLM, OpenAI ingress; SURVEY.md §2.3 M5). The
engine is TPU-native: continuous batching over a static-shape slot KV
cache (the scheduler, engine.py), jitted prefill/decode programs a model
(<name>_serving.py, found by config.SERVING_MODULES) behind one contract
with on-device sampling (served.py).
"""

from ray_tpu.llm.config import LLMConfig, SamplingParams
from ray_tpu.llm.engine import GenerationResult, LLMEngine
from ray_tpu.llm.serving import (
    LLMServer,
    build_llm_deployment,
    build_openai_app,
)
from ray_tpu.llm.hf import config_from_hf, convert_hf_llama
from ray_tpu.llm.tokenizer import ByteTokenizer, get_tokenizer

__all__ = [
    "LLMConfig", "SamplingParams", "LLMEngine", "GenerationResult",
    "LLMServer", "build_llm_deployment", "build_openai_app",
    "ByteTokenizer", "get_tokenizer", "convert_hf_llama", "config_from_hf",
]

# usage telemetry (local-only, opt-out — reference: usage_lib auto-records
# library imports)
try:
    from ray_tpu.usage import record_library_usage as _rec
    _rec("llm")
except Exception:
    pass
