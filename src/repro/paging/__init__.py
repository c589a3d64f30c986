"""Paged KV cache (DESIGN.md §9): block-pool allocator, paged cache arrays,
and `PagedBackend`, the one cache store the scheduler and `Engine` use.

Import graph note: ``paged_cache``/``block_pool`` are leaves (no serving
imports) so the serving engine can use `PagedCache` without a cycle;
``backend`` sits on top of serving.
"""
from repro.paging.block_pool import (  # noqa: F401
    BlockPool,
    PagingConfig,
    PoolExhausted,
    blocks_for_tokens,
)
from repro.paging.paged_cache import (  # noqa: F401
    PagedCache,
    build_table,
    init_paged_cache,
    max_blocks_per_row,
    paged_append_token,
    paged_to_slot,
    paginate_rows,
)
from repro.paging.backend import PagedBackend  # noqa: F401
