"""Autoregressive serving: paged (block-table) KV cache with copy-on-write
prefix sharing, cached single-query decode, continuous-batching engine,
sampling. See serving/engine.py for the design overview;
`ParallelInference(inference_mode=InferenceMode.GENERATE)` exposes the
engine behind the existing inference API."""
from deeplearning4j_tpu.serving.block_table import (BlockAllocator,
                                                    PrefixRegistry)
from deeplearning4j_tpu.serving.decode import (StackDecoder,
                                               decode_attention_paged,
                                               decode_attention_spec_paged,
                                               one_hot_embedder)
from deeplearning4j_tpu.serving.engine import (GenerationResult, Request,
                                               ServingEngine)
from deeplearning4j_tpu.serving.kv_cache import KVCache, init_cache_state
from deeplearning4j_tpu.serving.lifecycle import (HostBlockPool,
                                                  KVLifecycleManager,
                                                  PersistentPrefixStore,
                                                  resolve_lifecycle,
                                                  resolve_prefix_store)
from deeplearning4j_tpu.serving.loadgen import (LoadResult, LoadSpec,
                                                RequestOutcome,
                                                ScheduledRequest,
                                                build_schedule, run_spec)
from deeplearning4j_tpu.serving.sampler import (Sampler, sample_tokens,
                                                spec_accept_tokens)
from deeplearning4j_tpu.serving.sharding import (ShardedServingEngine,
                                                 ShardedServingGroup,
                                                 build_serving_mesh,
                                                 cache_partition_specs,
                                                 head_sharded_paged_attention,
                                                 head_sharded_spec_attention,
                                                 make_shard_and_gather_fns,
                                                 match_partition_rules,
                                                 resolve_replicas, resolve_tp,
                                                 serving_partition_rules)
from deeplearning4j_tpu.serving.spec import (NgramDraftIndex,
                                             resolve_spec_decode,
                                             resolve_spec_draft)

__all__ = [
    "KVCache", "init_cache_state", "BlockAllocator", "PrefixRegistry",
    "HostBlockPool", "KVLifecycleManager", "PersistentPrefixStore",
    "resolve_lifecycle", "resolve_prefix_store",
    "StackDecoder", "decode_attention_paged",
    "decode_attention_spec_paged",
    "one_hot_embedder", "ServingEngine", "Request", "GenerationResult",
    "Sampler", "sample_tokens", "spec_accept_tokens",
    "NgramDraftIndex", "resolve_spec_decode", "resolve_spec_draft",
    "LoadSpec", "LoadResult", "RequestOutcome", "ScheduledRequest",
    "build_schedule", "run_spec",
    "ShardedServingEngine", "ShardedServingGroup", "build_serving_mesh",
    "cache_partition_specs", "head_sharded_paged_attention",
    "head_sharded_spec_attention",
    "make_shard_and_gather_fns", "match_partition_rules",
    "resolve_replicas", "resolve_tp", "serving_partition_rules",
]
