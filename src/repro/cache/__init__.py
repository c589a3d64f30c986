"""Slot-layout budgeted KV cache (FairKV-native)."""
from repro.cache.slot_cache import (  # noqa: F401
    PlanArrays,
    SlotCache,
    fill_from_selection,
    init_cache,
    ring_write_index,
)
