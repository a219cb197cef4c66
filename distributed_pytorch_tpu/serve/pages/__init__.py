"""serve/pages — paged, prefix-shared KV cache for the serving engine.

The production observation (ROADMAP item 4; the Gemma-on-TPU serving
comparison in PAPERS.md): at consumer traffic scale the dominant
prefill bytes are IDENTICAL system prompts and few-shot headers,
recomputed per request. This subsystem computes each shared prefix
once: KV lives in a refcounted block pool (``pool``), full prompt
pages are keyed in a radix index (``prefix``), and an admitted request
reuses every resident page of its longest matching prefix — tail-only
prefill, LRU eviction of refcount-zero pages, typed back-pressure when
the pool is dry. ``PagedSlotPool`` (``cache``) is the engine's one
pool. docs/serving.md has the layout, lifecycle, and failure model.
"""

from .cache import PagedSlotPool, chunk_tokens  # noqa: F401
from .pool import PagePool  # noqa: F401
from .prefix import PrefixIndex  # noqa: F401

__all__ = ["PagePool", "PagedSlotPool", "PrefixIndex", "chunk_tokens"]
