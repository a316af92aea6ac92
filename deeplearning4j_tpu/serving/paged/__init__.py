"""Paged KV serving: block-pooled KV caches, prefix caching, and
tensor-parallel prefill/decode over the continuous-batching scheduler.

See docs/serving.md "Paged KV & prefix caching" and the module
docstrings of :mod:`.pool` (the allocator/prefix-cache bookkeeping) and
:mod:`.server` (the server itself).
"""
from deeplearning4j_tpu.serving.paged.pool import (NULL_BLOCK, BlockPool,
                                                   KVLeaf,
                                                   KVLeafUnsupportedError,
                                                   KVTier,
                                                   PoolExhaustedError,
                                                   blocks_for_tokens,
                                                   prefix_block_hashes,
                                                   table_widths)
from deeplearning4j_tpu.serving.paged.server import (
    PagedGenerativeServer, PagedGenerativeSpec, PagedMetrics,
    PrefixCacheUnsupportedError)

__all__ = ["BlockPool", "PoolExhaustedError", "NULL_BLOCK", "KVTier",
           "KVLeaf", "KVLeafUnsupportedError",
           "prefix_block_hashes", "blocks_for_tokens", "table_widths",
           "PagedGenerativeSpec", "PagedGenerativeServer", "PagedMetrics",
           "PrefixCacheUnsupportedError"]
