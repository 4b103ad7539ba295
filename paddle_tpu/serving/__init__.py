"""paddle_tpu.serving — dynamic-batching TPU inference serving.

Role parity: Paddle Serving / the reference's server-side inference
deployment story, rebuilt TPU-native over the compile-once Predictor:

- shape buckets (buckets.py) pin the executable universe so the
  Executor compile cache never storms under variable-length traffic;
- a dynamic micro-batcher (batcher.py) coalesces concurrent requests
  into padded bucket batches with bounded-queue backpressure and
  per-request deadlines;
- ``Server`` (server.py) AOT-warms every bucket at start, serves
  ``/stats`` + ``/health`` over the fleet KV HTTP server, and drains
  gracefully on stop;
- the GENERATIVE path (decode.py + kv_cache.py; the served model is
  transformer_lm.py): ``DecodeEngine`` runs autoregressive decode
  over a fixed slot batch with a paged,
  device-resident KV cache (Pallas paged-attention kernels on TPU),
  continuous batching at step boundaries, streaming token replies,
  and deadline reaping mid-decode; prefix-cache page sharing
  (``PrefixIndex`` refcounts + copy-on-write) lets same-prefix
  prompts skip both HBM and prefill compute, chunked prefill keeps
  long prompts from stalling the slot batch, and speculative
  decoding (draft model + one batched verify) multiplies greedy
  tokens-per-dispatch bitwise-losslessly; ``DecodeServer``
  replicates N engines behind one least-loaded admission point with
  per-replica ``/stats``.
"""
from .batcher import Batcher, InferenceRequest, RequestBase  # noqa: F401
from .buckets import (  # noqa: F401
    BucketSpec,
    DeadlineExceededError,
    QueueFullError,
    RequestAbandonedError,
    RequestTooLargeError,
    ServerClosedError,
    ServingError,
    prefill_bucket_grid,
)
from .decode import (  # noqa: F401
    DecodeConfig,
    DecodeEngine,
    DecodeRequest,
    TransformerLM,
    quantize_moe_weights,
    shard_moe_weights,
)
from .disagg import (  # noqa: F401
    Autoscaler,
    DisaggConfig,
    DisaggRequest,
    DisaggServer,
)
from .conv_moe_lm import ConvMoELM  # noqa: F401
from .gated_delta_lm import GatedDeltaLM  # noqa: F401
from .hybrid_moe_lm import HybridMoELM  # noqa: F401
from .indexed_moe_lm import IndexedMoELM  # noqa: F401
from .kv_cache import (  # noqa: F401
    CacheConfig,
    CacheExhaustedError,
    KVPageExport,
    PagedKVCache,
    PageAllocator,
    PrefixIndex,
)
from .latent_moe_lm import LatentMoELM  # noqa: F401
from .linear_latent_lm import LinearLatentLM  # noqa: F401
from .looped_lm import LoopedLM  # noqa: F401
from .mamba_lm import MambaLM  # noqa: F401
from .parallel_moe_lm import ParallelMoELM  # noqa: F401
from .window_moe_lm import WindowMoELM  # noqa: F401
from .server import (  # noqa: F401
    DecodeServer,
    Server,
    ServingConfig,
    least_loaded_order,
)

__all__ = [
    "Autoscaler", "Batcher", "BucketSpec", "CacheConfig",
    "CacheExhaustedError", "ConvMoELM", "DeadlineExceededError",
    "DecodeConfig",
    "DecodeEngine", "DecodeRequest", "DecodeServer", "DisaggConfig",
    "DisaggRequest", "DisaggServer", "GatedDeltaLM", "HybridMoELM",
    "IndexedMoELM", "InferenceRequest",
    "KVPageExport", "LatentMoELM", "LinearLatentLM", "LoopedLM",
    "MambaLM", "PageAllocator",
    "PagedKVCache",
    "ParallelMoELM",
    "PrefixIndex",
    "QueueFullError", "RequestAbandonedError", "RequestBase",
    "RequestTooLargeError", "Server", "ServerClosedError",
    "ServingConfig", "ServingError", "TransformerLM", "WindowMoELM",
    "least_loaded_order", "prefill_bucket_grid", "quantize_moe_weights",
    "shard_moe_weights",
]
