"""Tokenizers for the serving engine.

Loads a HuggingFace tokenizer when a local checkpoint path is given;
otherwise falls back to a deterministic byte-level tokenizer (vocab 256 +
specials) so the whole serving stack runs hermetically in CI with
random-weight models. Both expose the same minimal interface:
encode/decode, chat templating, eos/bos ids, and incremental detokenize.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field
from typing import Any


class ByteTokenizer:
    """Byte-level tokenizer: ids 0..255 are raw bytes; specials follow."""

    def __init__(self) -> None:
        self.bos_token_id = 256
        self.eos_token_id = 257
        self.vocab_size = 258

    def encode(self, text: str, add_bos: bool = True) -> list[int]:
        ids = list(text.encode("utf-8"))
        return ([self.bos_token_id] + ids) if add_bos else ids

    def decode(self, ids: list[int]) -> str:
        data = bytes(i for i in ids if 0 <= i < 256)
        return data.decode("utf-8", errors="replace")

    def apply_chat_template(self, messages: list[dict[str, Any]]) -> list[int]:
        parts = []
        for m in messages:
            content = m.get("content") or ""
            if not isinstance(content, str):  # multimodal union content
                content = " ".join(
                    p.get("text", "") for p in content if isinstance(p, dict) and p.get("type") == "text"
                )
            parts.append(f"<|{m.get('role', 'user')}|>\n{content}\n")
        parts.append("<|assistant|>\n")
        return self.encode("".join(parts))


class HFTokenizer:
    """transformers-backed tokenizer with chat-template support."""

    def __init__(self, path: str) -> None:
        from transformers import AutoTokenizer

        self._tok = AutoTokenizer.from_pretrained(path)
        self.bos_token_id = self._tok.bos_token_id
        self.eos_token_id = self._tok.eos_token_id
        self.vocab_size = len(self._tok)

    def encode(self, text: str, add_bos: bool = True) -> list[int]:
        return self._tok.encode(text, add_special_tokens=add_bos)

    def decode(self, ids: list[int]) -> str:
        return self._tok.decode(ids, skip_special_tokens=True)

    def apply_chat_template(self, messages: list[dict[str, Any]]) -> list[int]:
        if getattr(self._tok, "chat_template", None):
            return self._tok.apply_chat_template(messages, add_generation_prompt=True)
        fallback = ByteTokenizer()
        text_ids = fallback.apply_chat_template(messages)
        return self.encode(fallback.decode(text_ids))


@dataclass
class DetokenizeState:
    """Incremental detokenization: emit only complete, stable text."""

    ids: list[int] = field(default_factory=list)
    emitted: str = ""

    def push(self, tokenizer, token_id: int) -> str:
        self.ids.append(token_id)
        text = tokenizer.decode(self.ids)
        # Hold back trailing replacement chars (partial UTF-8 sequences).
        while text.endswith("�"):
            text = text[:-1]
        if not text.startswith(self.emitted):
            delta = text  # tokenizer rewrote history; re-emit from scratch
        else:
            delta = text[len(self.emitted):]
        self.emitted = text if text.startswith(self.emitted) else self.emitted + delta
        return delta


def load_tokenizer(path_or_name: str | None):
    """HF tokenizer for a checkpoint path; the byte tokenizer for presets
    (no path) — and, logged, when the HF one cannot be loaded: serving
    goes on hermetically, but with 258 ids of a larger vocabulary."""
    if path_or_name:
        try:
            return HFTokenizer(path_or_name)
        except Exception as e:
            logging.getLogger(__name__).warning(
                "tokenizer %r failed to load (%s: %s); serving with the byte tokenizer",
                path_or_name, type(e).__name__, e)
    return ByteTokenizer()
