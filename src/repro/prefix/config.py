"""`PrefixConfig`: knobs for shared-prefix reuse + chunked prefill.

One frozen dataclass, carried on `EngineConfig.prefix` and threaded into the
scheduler. ``enabled`` turns on the content-addressed prefix index (block
sharing across requests, DESIGN.md §14); ``chunk_tokens`` > 0 turns on
chunked prefill (prompts processed ``chunk_tokens`` at a time, interleaved
with decode ticks). The two compose but are independent — chunked prefill
works on any backend/executor, while block *sharing* additionally requires
the paged backend with an unpartitioned pool (§14 explains why).
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class PrefixConfig:
    """Prefix-cache + chunked-prefill configuration.

    enabled        — content-addressed prefix index: prompt-prefix blocks of
                     earlier requests are shared (refcounted) with later
                     requests whose prompts start with the same tokens.
                     Requires ``chunk_tokens > 0`` (hash-chain granularity
                     is the chunk) and a single-partition pool.
    chunk_tokens   — split prompt prefill into fixed chunks of this many
                     tokens, interleaved with decode ticks; 0 = monolithic
                     prefill (the pre-PR-8 behavior).
    max_entries    — LRU capacity of the prefix index (unpinned entries are
                     evicted beyond this, and on demand under pool pressure).
    """

    enabled: bool = False
    chunk_tokens: int = 0
    max_entries: int = 256

    def __post_init__(self):
        if self.chunk_tokens < 0:
            raise ValueError(
                f"chunk_tokens must be >= 0, got {self.chunk_tokens}")
        if self.enabled and self.chunk_tokens <= 0:
            raise ValueError(
                "prefix sharing requires chunked prefill: set chunk_tokens "
                "> 0 (the hash-chain is computed at chunk granularity)")
        if self.max_entries < 1:
            raise ValueError(
                f"max_entries must be >= 1, got {self.max_entries}")
