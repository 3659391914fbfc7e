"""Tokenizers for the LLM engine.

The reference delegates tokenization to HF/vLLM (reference:
ray.llm._internal.batch stages — chat-template → tokenize →  engine →
detokenize). Here: a dependency-free byte-level tokenizer for tests/dev and
an optional HF loader when a local tokenizer path is provided (no network
egress in this environment).
"""

from __future__ import annotations


class ByteTokenizer:
    """Byte-level: ids 0..255 are bytes; specials above."""

    def __init__(self, vocab_size: int = 512):
        assert vocab_size >= 259
        self.vocab_size = vocab_size
        self.bos_id = 256
        self.eos_id = 257
        self.pad_id = 258

    def encode(self, text: str, add_bos: bool = True) -> list[int]:
        ids = list(text.encode("utf-8"))
        return ([self.bos_id] + ids) if add_bos else ids

    def decode(self, ids: list[int]) -> str:
        """Bytes decode as UTF-8 and the three specials vanish. Any other id
        (from a model whose vocabulary is larger than this tokenizer's) is
        written as ``<|id|>``: text never hides a token, so two outputs
        compare equal only if their tokens do."""
        out = bytearray()
        for i in ids:
            if i < 256:
                out.append(i)
            elif i > self.pad_id:
                out += f"<|{i}|>".encode()
        return out.decode("utf-8", errors="replace")

    def apply_chat_template(self, messages: list[dict]) -> str:
        parts = []
        for m in messages:
            parts.append(f"<|{m['role']}|>\n{m['content']}\n")
        parts.append("<|assistant|>\n")
        return "".join(parts)


class HFTokenizer:
    """Wraps a locally available HF tokenizer directory."""

    def __init__(self, path: str):
        from transformers import AutoTokenizer

        self._tok = AutoTokenizer.from_pretrained(path)
        self.vocab_size = self._tok.vocab_size
        self.bos_id = self._tok.bos_token_id
        self.eos_id = self._tok.eos_token_id
        self.pad_id = self._tok.pad_token_id or self.eos_id

    def encode(self, text: str, add_bos: bool = True) -> list[int]:
        return self._tok.encode(text, add_special_tokens=add_bos)

    def decode(self, ids: list[int]) -> str:
        return self._tok.decode(ids, skip_special_tokens=True)

    def apply_chat_template(self, messages: list[dict]) -> str:
        try:
            return self._tok.apply_chat_template(messages, tokenize=False,
                                                 add_generation_prompt=True)
        except Exception:
            return ByteTokenizer.apply_chat_template(self, messages)  # type: ignore[arg-type]


def get_tokenizer(spec: str):
    if spec == "byte":
        return ByteTokenizer()
    return HFTokenizer(spec)
