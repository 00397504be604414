"""Multi-chip sharded serving: tensor-parallel decode + replica groups.

ISSUE 10 (beyond-reference; Orca OSDI '22 replica scheduling +
PagedAttention SOSP '23 KV framing; fmengine-style partition rules from
SNIPPETS.md). Two orthogonal axes on one `(replica, tensor)` device mesh:

- TENSOR parallelism (`ShardedServingEngine`): one engine whose params
  and paged KV pool are head-sharded over the `tensor` axis. Attention is
  head-local — q/k/v projections are column-parallel (whole heads per
  shard, GQA grouping preserved by contiguous splits whenever the TP
  degree divides n_kv_heads), the paged decode kernel runs unchanged per
  shard under shard_map (ops/decode_attention.paged_decode_specs), and
  the only cross-chip collective per decode step is the all-reduce GSPMD
  inserts for the row-parallel output projection. Block tables, lengths,
  and every scheduler-visible array stay replicated, so the host
  scheduler is UNTOUCHED: same admission, same chunking, same sync
  count per token (tests assert host-sync bit-parity).

- DATA parallelism (`ShardedServingGroup`): N independent engine
  replicas, each on its own row of the mesh (parallel/mesh.py
  `replica_submeshes`), behind one submit()/step()/stats() facade that
  ParallelInference drives exactly like a single engine. Routing is
  prefix-affinity first (read-only PrefixRegistry.match against each
  replica's registry, so identical prompts land where their KV already
  lives), then cohort affinity for not-yet-resident prompts, then
  least-loaded with a round-robin tie-break over existing stats()
  snapshots. Each replica gets a child telemetry registry parented to
  the group's (the parent/child adoption in telemetry/registry.py was
  built for this), so per-replica metrics stay isolated while the
  process-wide /metrics exposition aggregates all of them.

Env knobs: `DL4J_TPU_TP` (tensor-parallel degree) and
`DL4J_TPU_REPLICAS` (engine replicas); both default 1 and multiply to
the device requirement. All shapes are CPU-testable via
`XLA_FLAGS=--xla_force_host_platform_device_count=8`.
"""
from __future__ import annotations

import os
import re
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.telemetry import journal as _journal
from deeplearning4j_tpu.ops.decode_attention import (paged_decode_specs,
                                                     paged_spec_decode_specs)
from deeplearning4j_tpu.parallel.mesh import (compat_shard_map, make_mesh,
                                              replica_submeshes)
from deeplearning4j_tpu.serving.block_table import PrefixRegistry
from deeplearning4j_tpu.serving.radix_tree import (RadixPrefixTree,
                                                   resolve_prefix_radix)
from deeplearning4j_tpu.serving.decode import (StackDecoder,
                                               decode_attention_paged,
                                               decode_attention_spec_paged)
from deeplearning4j_tpu.serving.engine import Request, ServingEngine
from deeplearning4j_tpu.serving.kv_cache import resolve_block_size
from deeplearning4j_tpu.serving.lifecycle import resolve_prefix_store
from deeplearning4j_tpu.serving.policy import resolve_policy

__all__ = [
    "match_partition_rules", "make_shard_and_gather_fns", "named_tree_map",
    "serving_partition_rules", "cache_partition_specs",
    "resolve_tp", "resolve_replicas", "build_serving_mesh",
    "head_sharded_paged_attention", "head_sharded_spec_attention",
    "ShardedServingEngine", "ShardedServingGroup", "GROUP_SUMMED_KEYS",
]


def _is_spec(x) -> bool:
    return isinstance(x, P)


# Engine-lifetime counters and point-in-time gauges that
# ShardedServingGroup.stats() sums replica-wise into the fleet view.
# Pinned by tests/test_sharded_serving.py: every key must exist in
# ServingEngine.stats(), and new fleet-meaningful counters belong HERE —
# PR 11's spec-decode counters were silently dropped from the aggregate
# exactly because this list was inlined and easy to forget.
GROUP_SUMMED_KEYS: Tuple[str, ...] = (
    "host_syncs", "tokens_out", "queue_depth", "active_slots",
    "free_slots", "kv_blocks_free", "kv_blocks_shared", "kv_rejections",
    "prefix_hits", "prefix_shared_tokens", "prefill_chunks",
    "nonfinite_chunks", "admission_retries",
    "spec_tokens_accepted", "spec_tokens_rejected",
    "kv_evictions_recompute", "kv_evictions_swap", "kv_preemptions",
    "kv_swap_out_bytes", "kv_swap_in_bytes", "kv_host_pool_bytes",
    "prefix_store_hits", "prefix_store_tokens",
    # ISSUE 16: radix-tree residency + popular-prefix signal, fleet-wide
    "prefix_lineage_hits", "kv_blocks_cached",
    # ISSUE 17: disaggregated prefill/decode — cross-replica KV
    # migration volume and the per-role admission split
    "kv_transfer_out", "kv_transfer_in", "kv_transfer_bytes",
    "role_prefill_requests", "role_decode_requests",
    # ISSUE 18: hierarchical KV storage — disk-tier traffic, async
    # swap-out harvests, and lost-spill recompute fallbacks, fleet-wide
    "kv_disk_pool_bytes", "kv_disk_demotions", "kv_disk_promotions",
    "kv_swap_harvests", "kv_pending_swaps", "kv_swap_lost",
    # ISSUE 14: group snapshot_seq = per-replica scheduler-iteration
    # counters summed — still strictly monotonic while any replica steps,
    # so scrapers can detect stale/torn fleet snapshots the same way
    "snapshot_seq",
    # ISSUE 19: SLO verdicts and burn-rate alerts, fleet-wide (both are
    # plain counters that read 0 on engines without a budget/monitor)
    "slo_violations", "alerts_total",
)


# --------------------------------------------------------- partition rules
def _path_name(path) -> str:
    """'/'-joined name for a pytree key path ("0/w_q" for params[0]["w_q"])."""
    parts = []
    for k in path:
        if isinstance(k, jax.tree_util.SequenceKey):
            parts.append(str(k.idx))
        elif isinstance(k, jax.tree_util.DictKey):
            parts.append(str(k.key))
        elif isinstance(k, jax.tree_util.GetAttrKey):
            parts.append(k.name)
        else:
            parts.append(str(k))
    return "/".join(parts)


def named_tree_map(fn, tree, is_leaf=None):
    """tree_map where `fn(name, leaf)` sees the '/'-joined key path — the
    addressing scheme the regex partition rules match against."""
    return jax.tree_util.tree_map_with_path(
        lambda path, x: fn(_path_name(path), x), tree, is_leaf=is_leaf)


def match_partition_rules(rules: Sequence[Tuple[str, P]], params):
    """Map every param leaf to a PartitionSpec by regex over its path name
    (the fmengine pattern, SNIPPETS.md): scalars and single-element leaves
    are always replicated, otherwise the FIRST rule whose pattern
    re.search-matches the '/'-joined path wins, and an unmatched leaf is a
    hard error — silent replication of a tensor someone meant to shard is
    how HBM budgets quietly blow up."""
    def match(name, leaf):
        if getattr(leaf, "ndim", 0) == 0 or int(np.prod(np.shape(leaf))) == 1:
            return P()
        for pattern, spec in rules:
            if re.search(pattern, name) is not None:
                return spec
        raise ValueError(f"no partition rule matched param {name!r} "
                         f"(shape {np.shape(leaf)}); add a rule or an "
                         "explicit catch-all")
    return named_tree_map(match, params)


def make_shard_and_gather_fns(partition_specs, mesh: Mesh):
    """Per-leaf `(shard_fns, gather_fns)` trees for a spec tree: shard_fns
    device_put leaves onto `mesh` under their spec; gather_fns pull a
    sharded leaf back to a single host ndarray (checkpoint/debug path —
    NEVER the decode hot loop)."""
    def make_shard(spec):
        sharding = NamedSharding(mesh, spec)

        def shard_fn(x):
            return jax.device_put(x, sharding)
        return shard_fn

    def make_gather(spec):
        del spec    # gather is always to fully-replicated host memory

        def gather_fn(x):
            # sync-ok: explicit gather-to-host entry point (checkpointing)
            return np.asarray(jax.device_put(x, NamedSharding(mesh, P())))
        return gather_fn

    shard_fns = jax.tree_util.tree_map(make_shard, partition_specs,
                                       is_leaf=_is_spec)
    gather_fns = jax.tree_util.tree_map(make_gather, partition_specs,
                                        is_leaf=_is_spec)
    return shard_fns, gather_fns


def serving_partition_rules(tensor_axis: str = "tensor"):
    """Partition rules for a StackDecoder param stack (list-of-dicts,
    paths like "0/w_q"). Megatron-style within each attention layer:
    q/k/v projections column-parallel (the head dim is the contiguous
    column tail, so a contiguous split is a whole-heads split), the
    output projection row-parallel (its all-reduce is THE per-step
    collective), biases and every position-wise layer replicated."""
    col = P(None, tensor_axis)
    row = P(tensor_axis, None)
    return [
        (r"w_q$", col),
        (r"w_k$", col),
        (r"w_v$", col),
        (r"w_o$", row),
        # everything else (attention bias, output head W/b, position-wise
        # layers) is small relative to the KV pool: replicate
        (r".", P()),
    ]


def cache_partition_specs(tensor_axis: str = "tensor",
                          quantized: bool = False) -> Dict[str, P]:
    """Specs for the paged cache pytree (kv_cache.init_cache_state):
    k/v pools `(n_layers, num_blocks+1, block_size, Hk, D)` sharded on the
    kv-head axis, lengths and block tables replicated (the host scheduler
    reads and writes them; they are bytes-trivial). A quantized pool
    (ISSUE 15) adds per-head-per-block scale arrays
    `(n_layers, num_blocks+1, Hk)` — scales shard WITH their heads, so
    each chip dequantizes its own head slice with zero collectives."""
    heads = P(None, None, None, tensor_axis, None)
    specs = {"k": heads, "v": heads, "lengths": P(), "block_tables": P()}
    if quantized:
        specs["k_scale"] = P(None, None, tensor_axis)
        specs["v_scale"] = P(None, None, tensor_axis)
    return specs


# ------------------------------------------------------------- env knobs
def _resolve_degree(explicit, env: str) -> int:
    v = int(explicit) if explicit is not None \
        else int(os.environ.get(env, "1"))
    if v < 1:
        raise ValueError(f"{env} must be >= 1, got {v}")
    return v


def resolve_tp(explicit: Optional[int] = None) -> int:
    """Tensor-parallel degree: explicit arg, else $DL4J_TPU_TP, else 1."""
    return _resolve_degree(explicit, "DL4J_TPU_TP")


def resolve_replicas(explicit: Optional[int] = None) -> int:
    """Engine replica count: explicit arg, else $DL4J_TPU_REPLICAS, else 1."""
    return _resolve_degree(explicit, "DL4J_TPU_REPLICAS")


def build_serving_mesh(replicas: int, tp: int,
                       replica_axis: str = "replica",
                       tensor_axis: str = "tensor") -> Mesh:
    """The `(replica, tensor)` serving mesh: row r = replica r's TP group."""
    return make_mesh(replicas * tp, axes=(replica_axis, tensor_axis),
                     shape=(replicas, tp))


# ------------------------------------------------- head-sharded attention
def head_sharded_paged_attention(mesh: Mesh, tensor_axis: str = "tensor"):
    """A drop-in for serving.decode.decode_attention_paged that runs the
    SAME kernel (Pallas split-K on TPU, dense paged fallback elsewhere)
    per head-shard under shard_map. Head-local attention needs no
    collective in the body (see paged_decode_specs), so TP changes only
    WHERE heads run, not what they compute. A quantized pool (ISSUE 15)
    passes k_scale/v_scale — the scale arrays split on THEIR head axis
    alongside the pool, so dequant stays chip-local too."""
    in_specs, out_spec = paged_decode_specs(tensor_axis)
    in_specs_q, _ = paged_decode_specs(tensor_axis, quantized=True)

    def attention(q, kp, vp, block_tables, visible, scale, window: int = 0,
                  k_scale=None, v_scale=None):
        if k_scale is None:
            def local(qs, kps, vps, bt, vis):
                return decode_attention_paged(qs, kps, vps, bt, vis, scale,
                                              window)
            sharded = compat_shard_map(local, mesh, in_specs, out_spec)
            return sharded(q, kp, vp, block_tables, visible)

        def local_q(qs, kps, vps, bt, vis, ks, vs):
            return decode_attention_paged(qs, kps, vps, bt, vis, scale,
                                          window, k_scale=ks, v_scale=vs)
        sharded = compat_shard_map(local_q, mesh, in_specs_q, out_spec)
        return sharded(q, kp, vp, block_tables, visible, k_scale, v_scale)

    return attention


def head_sharded_spec_attention(mesh: Mesh, tensor_axis: str = "tensor"):
    """Head-sharded multi-query VERIFY attention for speculative decoding
    (ISSUE 11): the widened query tile (S, Q, H, D) splits on the head
    axis exactly like single-query decode, so the spec kernel runs
    head-local under shard_map with ZERO new collectives — verification
    costs the same communication as one plain decode step. Quantized
    pools (ISSUE 15) ride k_scale/v_scale head-sharded the same way."""
    in_specs, out_spec = paged_spec_decode_specs(tensor_axis)
    in_specs_q, _ = paged_spec_decode_specs(tensor_axis, quantized=True)

    def attention(q, kp, vp, block_tables, visible, scale, window: int = 0,
                  k_scale=None, v_scale=None):
        if k_scale is None:
            def local(qs, kps, vps, bt, vis):
                return decode_attention_spec_paged(qs, kps, vps, bt, vis,
                                                   scale, window)
            sharded = compat_shard_map(local, mesh, in_specs, out_spec)
            return sharded(q, kp, vp, block_tables, visible)

        def local_q(qs, kps, vps, bt, vis, ks, vs):
            return decode_attention_spec_paged(qs, kps, vps, bt, vis, scale,
                                               window, k_scale=ks,
                                               v_scale=vs)
        sharded = compat_shard_map(local_q, mesh, in_specs_q, out_spec)
        return sharded(q, kp, vp, block_tables, visible, k_scale, v_scale)

    return attention


# ------------------------------------------------------ tensor-parallel TP
class ShardedServingEngine(ServingEngine):
    """A ServingEngine whose decoder params and paged KV pool live
    head-sharded on a single-axis tensor mesh.

    Same host scheduler, same API, same token stream (greedy decode is
    bit-identical to the single-chip engine; fp64 oracle parity holds to
    1e-9): the only differences are WHERE tensors live and the per-chip
    byte accounting — `serving.kv_bytes_resident` / `kv_cache_bytes`
    report PER-DEVICE bytes (1/TP of the logical pool), which is the
    number capacity planning actually needs."""

    def __init__(self, net, max_seqs: int, max_len: int, *,
                 tp: Optional[int] = None, mesh: Optional[Mesh] = None,
                 tensor_axis: str = "tensor", **kw):
        # mesh/tp must exist before super().__init__ runs _build_decoder
        self.tensor_axis = tensor_axis
        if mesh is not None:
            if mesh.axis_names != (tensor_axis,):
                raise ValueError(f"expected a 1-axis ({tensor_axis!r},) "
                                 f"mesh, got axes {mesh.axis_names}")
            self.mesh = mesh
            self.tp = int(mesh.devices.size)
        else:
            self.tp = resolve_tp(tp)
            self.mesh = make_mesh(self.tp, axes=(tensor_axis,))
        super().__init__(net, max_seqs, max_len, **kw)
        cache = self.decoder.cache
        # per-DEVICE byte semantics: the pool is head-sharded, so each chip
        # holds 1/TP of every position's KV bytes (Hk % tp == 0 makes the
        # division exact)
        self._kv_bytes_per_pos = cache.bytes_per_position // self.tp
        # quantized-pool scale bytes split with their heads (Hk % tp == 0)
        self._kv_block_overhead = cache.block_overhead_bytes // self.tp
        self._g_kv_total.set(cache.bytes() // self.tp)
        self._g_params.set(self._sharded_param_bytes())
        self._g_tp = self.metrics.gauge(
            "serving.tensor_parallel", "tensor-parallel degree (heads are "
            "sharded over this many chips)")
        self._g_tp.set(self.tp)
        # pin the per-slot device state to the mesh (replicated) so eager
        # slot updates between iterations stay on the engine's devices
        rep = NamedSharding(self.mesh, P())
        self._hist = jax.device_put(self._hist, rep)
        self._last = jax.device_put(self._last, rep)
        self._plens = jax.device_put(self._plens, rep)
        self._eos = jax.device_put(self._eos, rep)
        self._maxgen = jax.device_put(self._maxgen, rep)

    # ------------------------------------------------------------- seams
    def _build_decoder(self, net, max_seqs, max_len, **kw) -> StackDecoder:
        dec = StackDecoder(
            net, max_seqs, max_len,
            paged_attention=head_sharded_paged_attention(self.mesh,
                                                         self.tensor_axis),
            paged_spec_attention=head_sharded_spec_attention(
                self.mesh, self.tensor_axis),
            **kw)
        tp = self.tp
        if dec.n_kv_heads % tp:
            raise ValueError(
                f"tensor-parallel degree {tp} does not divide n_kv_heads "
                f"{dec.n_kv_heads} — GQA head sharding needs whole kv "
                "heads per chip (lower DL4J_TPU_TP or widen the model)")
        for i in dec.attn_idx:
            layer = dec.layers[i]
            if layer.n_heads % tp:
                raise ValueError(
                    f"tensor-parallel degree {tp} does not divide layer "
                    f"{i}'s n_heads {layer.n_heads}")
        self._param_specs = match_partition_rules(
            serving_partition_rules(self.tensor_axis), dec.params)
        self._cache_specs = cache_partition_specs(
            self.tensor_axis, quantized=dec.cache.kv_quant)
        to_sharding = lambda spec: NamedSharding(self.mesh, spec)
        self._param_shardings = jax.tree_util.tree_map(
            to_sharding, self._param_specs, is_leaf=_is_spec)
        self._cache_shardings = {k: to_sharding(s)
                                 for k, s in self._cache_specs.items()}
        shard_fns, self._gather_fns = make_shard_and_gather_fns(
            self._param_specs, self.mesh)
        dec.params = jax.tree_util.tree_map(lambda f, x: f(x), shard_fns,
                                            dec.params)
        dec.cache.state = jax.device_put(dec.cache.state,
                                         self._cache_shardings)
        # pin pjit shardings on the decoder's own entry points so the
        # prefill and suffix/chunk passes are tensor-parallel end to end
        # (the scatter into the head-sharded pool partitions on Hk; the
        # dense prompt attention replicates — prompt activations are tiny
        # next to the pool)
        ps, cs = self._param_shardings, self._cache_shardings
        rep = NamedSharding(self.mesh, P())
        dec._prefill_jit = jax.jit(
            dec._prefill_fn,
            in_shardings=(ps, cs, rep, rep, rep),
            out_shardings=(cs, rep))
        dec._prefill_shared_jit = jax.jit(
            dec._prefill_shared_fn,
            static_argnums=(6,),
            in_shardings=(ps, cs, rep, rep, rep, rep),
            out_shardings=(cs, rep))
        dec._decode_jit = jax.jit(
            dec._decode_fn,
            in_shardings=(ps, cs, rep, rep),
            out_shardings=(cs, rep))
        return dec

    def _jit_decode(self, fn, kind: str):
        """Pin the engine step/chunk pjit shardings: cache pytree keeps its
        head-sharded placement across dispatches (no resharding between
        iterations), every scheduler array replicated."""
        rep = NamedSharding(self.mesh, P())
        # spec (ISSUE 11) takes two extra replicated inputs (draft ids +
        # per-slot draft lengths) and returns the commit bundle
        n_out = {"step": 6, "chunk": 7, "spec": 9}[kind]
        n_in = 10 if kind == "spec" else 8
        in_s = (self._param_shardings, self._cache_shardings) + \
            (rep,) * n_in
        out_s = (self._cache_shardings,) + (rep,) * (n_out - 1)
        return jax.jit(fn, in_shardings=in_s, out_shardings=out_s)

    def _sharded_param_bytes(self) -> int:
        """Per-device param bytes: tensor-sharded leaves count 1/TP."""
        total = 0
        leaves = jax.tree_util.tree_leaves(self.decoder.params)
        specs = jax.tree_util.tree_leaves(self._param_specs,
                                          is_leaf=_is_spec)
        for leaf, spec in zip(leaves, specs):
            nb = leaf.size * leaf.dtype.itemsize
            total += nb // self.tp if self.tensor_axis in spec else nb
        return total

    def stats(self) -> Dict[str, float]:
        s = super().stats()
        s["tp"] = self.tp
        return s


# --------------------------------------------------- data-parallel group
class ShardedServingGroup:
    """N independent (optionally tensor-parallel) engine replicas behind
    one engine-shaped facade: submit/step/drain/generate/start/shutdown/
    stats match ServingEngine, so ParallelInference and loadgen.run drive
    a group unchanged.

    Replicas share NOTHING on device — each owns a mesh row, its params,
    its KV pool, and its scheduler. What spans replicas is host-side:
    the admission router and the group telemetry registry (each engine's
    child registry is parented here, so the process /metrics exposition
    aggregates the fleet while per-replica stats stay isolated).

    Scheduling decisions live on ONE policy object (ISSUE 17,
    serving/policy.py), consulted under the group lock (host-only —
    zero device syncs). The default `ColocatedPolicy` routes exactly as
    the group always did: prefix affinity (the replica whose
    PrefixRegistry already holds the longest matching resident prefix)
    -> cohort affinity (prompts sharing a leading KV block follow the
    first of their kind, so a cohort's FIRST prompt seeds the registry
    the rest will hit) -> published-heat affinity (ISSUE 17 satellite:
    lineage heat replicas publish through the shared prefix store) ->
    least-loaded (queue_depth + active_slots) with a rotating
    round-robin tie-break. `DisaggregatedPolicy` (serving/disagg.py,
    or env DL4J_TPU_DISAGG=<n>) splits the replicas into PREFILL and
    DECODE roles: new requests route to prefill rows only, and each
    finished prefill's live KV ships to a decode row through the
    engines' transfer seam (`_transfer_from`)."""

    def __init__(self, net, max_seqs: int, max_len: int, *,
                 replicas: Optional[int] = None, tp: Optional[int] = None,
                 seed: int = 0, replica_axis: str = "replica",
                 tensor_axis: str = "tensor", metrics_parent=None,
                 **engine_kw):
        self.replicas = resolve_replicas(replicas)
        self.tp = resolve_tp(tp)
        self.mesh = build_serving_mesh(self.replicas, self.tp,
                                       replica_axis, tensor_axis)
        self.metrics = telemetry.MetricsRegistry(
            parent=metrics_parent if metrics_parent is not None
            else telemetry.registry())
        self._g_replicas = self.metrics.gauge(
            "serving.replicas", "data-parallel engine replicas in the group")
        self._g_replicas.set(self.replicas)
        self._c_routed = self.metrics.counter(
            "serving.router_requests", "requests routed by the group")
        self._c_affinity = self.metrics.counter(
            "serving.router_prefix_affinity", "requests routed to a replica "
            "because its registry already held a matching resident prefix")
        self._c_heat = self.metrics.counter(
            "serving.router_heat_affinity", "requests routed to a replica "
            "by published lineage heat (no resident match anywhere, but "
            "this replica recently served the prefix — ISSUE 17)")
        self._c_transfers = self.metrics.counter(
            "serving.router_transfers", "finished prefills handed from a "
            "prefill-role replica to a decode-role replica (ISSUE 17)")
        # fleet KV gauges (ISSUE 12): group-level names are disjoint from
        # the per-engine serving.kv.* observatory gauges, so the parented
        # prometheus exposition shows both layers without double counting
        self._g_fleet_free = self.metrics.gauge(
            "serving.kv.fleet_bytes_free", "free KV bytes summed across "
            "every replica's pool")
        self._g_fleet_shared = self.metrics.gauge(
            "serving.kv.fleet_bytes_shared", "prefix-shared KV bytes "
            "(each shared block counted once) summed across replicas")
        self._g_fleet_live = self.metrics.gauge(
            "serving.kv.fleet_bytes_private_live", "privately owned live "
            "KV bytes summed across replicas")
        self._g_fleet_waste = self.metrics.gauge(
            "serving.kv.fleet_bytes_waste", "tail + reserved-but-unwritten "
            "KV bytes summed across replicas")
        self._g_fleet_imbal = self.metrics.gauge(
            "serving.kv.fleet_imbalance", "max-min spread of per-replica "
            "used-block fraction (0 = perfectly balanced fleet)")
        block_size = resolve_block_size(engine_kw.get("kv_block"), max_len)
        # per-replica registry handles: owned (bound) by each replica's KV
        # pool, read by the router for affinity — block ids never cross
        # replicas (see block_table.PrefixRegistry.bind_pool). With the
        # radix tree on (ISSUE 16) each replica gets its own tree; the
        # router's longest-prefix affinity then routes a session's next
        # turn to the replica RETAINING its history, which is what makes
        # cross-turn reuse survive replica fan-out.
        reg_cls = (RadixPrefixTree
                   if resolve_prefix_radix(engine_kw.get("prefix_radix"))
                   else PrefixRegistry)
        self.registries = [reg_cls(block_size)
                           for _ in range(self.replicas)]
        # ONE persistent prefix store for the whole group (ISSUE 13):
        # unlike PrefixRegistry entries, store entries are content-keyed
        # BYTES (no pool-scoped block ids), so a prompt prefilled on one
        # replica is restorable on every other — resolved here so all
        # replicas share the same instance instead of each resolving its
        # own from the environment
        self.prefix_store = resolve_prefix_store(
            engine_kw.pop("prefix_store", None))
        # ONE scheduling-policy object for the whole group (ISSUE 17):
        # routing state (cohort map, rotation cursors) lives on it, and
        # every engine consults the SAME instance at its own decision
        # points (admission, TTL eviction)
        self.policy = resolve_policy(engine_kw.pop("policy", None)) \
            .bind(self.replicas)
        # ONE group-level decision journal (ISSUE 20, replica=-1): it owns
        # the cross-replica records (route/transfer) while each engine
        # journals its own admission/preempt/spec stream into a child
        # journal (a replica<r> subdirectory when persisting).
        # fleet_journal() merges them ordered by (tick, replica, seq).
        self.journal = _journal.resolve_journal(
            engine_kw.pop("journal", None), replica=-1)
        # group-journal records arrive from submit (group lock held) AND
        # from prefill engines' scheduler threads (_transfer_from, engine
        # lock held — taking the group lock there would deadlock against
        # submit's group-lock -> engine-lock order), so they serialize on
        # a dedicated leaf lock instead
        self._jlock = threading.Lock()
        # serial_step (ISSUE 20, env DL4J_TPU_GROUP_SERIAL): force
        # index-ordered serial stepping so cross-replica interactions
        # (prefill->decode KV adoption) land at a deterministic point in
        # every replica's tick stream — both journal recording and replay
        # of a group run require it
        serial = engine_kw.pop("serial_step", None)
        if serial is None:
            serial = os.environ.get(
                "DL4J_TPU_GROUP_SERIAL", "") not in ("", "0", "off")
        self.serial_step = bool(serial)
        self.engines: List[ShardedServingEngine] = []
        base_name = engine_kw.pop("name", None) or "replica"
        for r, submesh in enumerate(replica_submeshes(self.mesh,
                                                      tensor_axis)):
            eng = ShardedServingEngine(
                net, max_seqs, max_len, mesh=submesh,
                tensor_axis=tensor_axis, seed=seed + r,
                metrics_parent=self.metrics,
                prefix_registry=self.registries[r],
                prefix_store=self.prefix_store,
                policy=self.policy,
                name=f"{base_name}{r}",
                journal=(_journal.child_journal(self.journal, r)
                         if self.journal is not None else False),
                **engine_kw)
            # replica identity (ISSUE 14 satellite): labels the engine's
            # tracer track and flight-recorder records so multi-replica
            # Perfetto dumps are distinguishable
            eng.replica_id = r
            # disaggregation wiring (ISSUE 17): prefill-role engines get
            # the transfer callback that ships each finished prefill's
            # live KV to the decode row the policy picks
            eng.role = self.policy.role(r)
            if eng.role == "prefill":
                eng._transfer_cb = \
                    lambda act, _r=r: self._transfer_from(_r, act)
            self.engines.append(eng)
        self._lock = threading.Lock()
        # replicas are independent chips: drive them CONCURRENTLY per
        # step() so one replica's chunk dispatch never serializes behind
        # another's (each engine is only ever stepped by one worker at a
        # time — step() joins before returning). On a single-core host the
        # threads would only time-slice one processor and the contention
        # is pure loss, so the fan-out is capped at the core count.
        workers = 1 if self.serial_step \
            else min(self.replicas, os.cpu_count() or 1)
        self._pool = (ThreadPoolExecutor(
            max_workers=workers, thread_name_prefix="dl4j-replica")
            if workers > 1 else None)

    # ------------------------------------------------------------ routing
    def _fleet_view(self) -> Dict[str, object]:
        """The host-bookkeeping view the policy's route/transfer
        decisions read. `stats_fn` is lazy (one engine-lock snapshot
        per replica the policy actually inspects), so affinity hits
        never pay a stats() sweep — exactly the pre-policy behavior."""
        return {"registries": self.registries,
                "block_size": self.registries[0].block_size,
                "n": self.replicas,
                "store": self.prefix_store,
                "stats_fn": lambda r: self.engines[r].stats()}

    def _route(self, req: Request) -> int:
        replica, reason = self.policy.route(req, self._fleet_view())
        if reason == "prefix_affinity":
            self._c_affinity.inc()
        elif reason == "heat":
            self._c_heat.inc()
        if self.journal is not None:
            # tick = the ROUTED replica's allocator clock: the replayer
            # paces this arrival against that same clock (host attribute
            # read — no device touch)
            with self._jlock:
                self.journal.record(
                    "route",
                    tick=self.engines[replica].decoder.cache.allocator.clock,
                    dst=replica, reason=reason, plen=len(req.tokens))
        return replica

    def _transfer_from(self, src: int, act) -> None:
        """Prefill->decode hand-off (ISSUE 17), called from the SOURCE
        engine's scheduler thread with that engine's lock held: consult
        the policy for the decode target and adopt the request there.
        Deliberately takes NO group lock — the only lock acquired is
        the TARGET engine's (`_adopt`), keeping lock order prefill ->
        decode, one-directional (decode engines never call into prefill
        engines), so no cycle with submit's group-lock -> engine-lock
        path exists."""
        view = self._fleet_view()
        view["tokens"] = list(act.req.tokens)
        view["src"] = src
        target = self.policy.transfer(view)
        self._c_transfers.inc()
        dst = src if target is None else target
        if self.journal is not None:
            # journaled BEFORE the adopt so the transfer verdict precedes
            # the destination's xfer_in record in seq order
            with self._jlock:
                self.journal.record(
                    "transfer",
                    tick=self.engines[src].decoder.cache.allocator.clock,
                    src=src, dst=dst, req=act.req_id)
        # target is always a decode row when the callback is wired; the
        # src fallback is a safety net (src engine's RLock re-enters)
        self.engines[dst]._adopt(act)

    # --------------------------------------------------- engine-shaped API
    def submit(self, request):
        """Route to a replica and queue there; returns that engine's
        future."""
        req = request if isinstance(request, Request) else Request(request)
        with self._lock:
            replica = self._route(req)
            self._c_routed.inc()
        return self.engines[replica].submit(req)

    def step(self) -> bool:
        """One scheduler iteration on EVERY replica, concurrently (one
        worker per replica, joined before returning — the engines' own
        device streams already run independently; this keeps their HOST
        scheduling from serializing too). Returns True while any replica
        has active or queued work."""
        busy = False
        if self._pool is None:
            for engine in self.engines:
                busy = engine.step() or busy
            return busy
        for done in [self._pool.submit(e.step) for e in self.engines]:
            busy = done.result() or busy
        return busy

    def drain(self) -> None:
        while self.step():
            pass

    def generate(self, prompts, **kw):
        futs = [self.submit(p if isinstance(p, Request) else Request(p, **kw))
                for p in prompts]
        self.drain()
        return [f.get(timeout=0) for f in futs]

    def start(self) -> "ShardedServingGroup":
        for engine in self.engines:
            engine.start()
        return self

    def shutdown(self, wait: bool = True) -> None:
        for engine in self.engines:
            engine.shutdown(wait=wait)
        if self._pool is not None:
            self._pool.shutdown(wait=wait)
        if self.journal is not None:
            self.journal.flush()

    def fleet_journal(self) -> List[dict]:
        """The merged fleet decision stream (ISSUE 20): group-level
        route/transfer records (replica=-1) interleaved with every
        replica's own journal, ordered by (tick, replica, seq) — the
        input serving/replay.py's group replayer consumes."""
        journals = [j for j in [self.journal]
                    + [e.journal for e in self.engines] if j is not None]
        return _journal.merge_fleet(journals)

    def stats(self) -> Dict[str, object]:
        """Fleet view: lifetime counters summed across replicas
        (GROUP_SUMMED_KEYS), group-wide derived ratios recomputed from the
        sums (a mean of per-replica ratios would weight an idle replica
        like a saturated one), plus the per-replica snapshots (each taken
        under its engine's lock)."""
        per = [engine.stats() for engine in self.engines]
        agg: Dict[str, object] = {
            "replicas": self.replicas, "tp": self.tp,
            "router_requests": self._c_routed.value,
            "router_prefix_affinity": self._c_affinity.value,
            "router_heat_affinity": self._c_heat.value,
            "router_transfers": self._c_transfers.value,
            "policy": type(self.policy).__name__,
            "roles": [self.policy.role(r) for r in range(self.replicas)],
            "journal": (self.journal.stats()
                        if self.journal is not None else None),
            "per_replica": per,
        }
        for key in GROUP_SUMMED_KEYS:
            agg[key] = sum(s.get(key, 0) for s in per)
        agg["host_syncs_per_token"] = \
            agg["host_syncs"] / max(1, agg["tokens_out"])
        agg["spec_accept_rate"] = agg["spec_tokens_accepted"] / max(
            1, agg["spec_tokens_accepted"] + agg["spec_tokens_rejected"])
        agg["resident_seqs_max"] = max(
            (s.get("resident_seqs_max", 0) for s in per), default=0)
        # used-block imbalance straight from the per-replica snapshots —
        # num_blocks is a host attribute, so this stays sync-free
        fracs = [(e.decoder.cache.num_blocks - s["kv_blocks_free"])
                 / max(1, e.decoder.cache.num_blocks)
                 for e, s in zip(self.engines, per)]
        agg["kv_used_imbalance"] = \
            (max(fracs) - min(fracs)) if fracs else 0.0
        return agg

    def kv_fleet_snapshot(self) -> Dict[str, object]:
        """Fleet-wide KV memory attribution (ISSUE 12): one atomic pool
        snapshot per replica (each under its engine's scheduler lock),
        attributed via telemetry.kv_observatory.attribute_pool, summed
        into the group's serving.kv.fleet_* gauges. Per-replica entries
        keep their own attribution so a hot replica is visible next to an
        idle one; `imbalance` is the max-min spread of used-block
        fraction. Host-side bookkeeping only — zero device reads."""
        from deeplearning4j_tpu.telemetry.kv_observatory import \
            attribute_pool
        fleet = {"pool_bytes": 0, "free_bytes": 0, "shared_bytes": 0,
                 "private_live_bytes": 0, "waste_tail_bytes": 0,
                 "waste_reserved_bytes": 0, "cached_prefix_bytes": 0}
        per: List[Dict[str, object]] = []
        fracs: List[float] = []
        for r, engine in enumerate(self.engines):
            snap = engine.kv_pool_snapshot()
            att = attribute_pool(snap)
            for key in fleet:
                fleet[key] += att[key]
            n = snap["num_blocks"]
            used = n - snap["blocks_free"]
            fracs.append(used / max(1, n))
            per.append({"replica": r, "blocks_used": used,
                        "blocks_free": snap["blocks_free"],
                        "blocks_shared": snap["blocks_shared"],
                        "clock": snap["clock"],
                        "attribution": att})
        imbalance = (max(fracs) - min(fracs)) if fracs else 0.0
        self._g_fleet_free.set(fleet["free_bytes"])
        self._g_fleet_shared.set(fleet["shared_bytes"])
        self._g_fleet_live.set(fleet["private_live_bytes"])
        self._g_fleet_waste.set(fleet["waste_tail_bytes"]
                                + fleet["waste_reserved_bytes"])
        self._g_fleet_imbal.set(imbalance)
        return {**fleet, "imbalance": imbalance, "per_replica": per,
                "conserved": all(p["attribution"]["conserved"]
                                 for p in per)}

    def fleet_timeseries(self) -> Dict[str, object]:
        """Fleet time-series view (ISSUE 19): merge every timeseries-
        enabled replica's windowed summary into ONE fleet row —
        rates/queue depths SUM (fleet throughput is the sum of replica
        throughputs), quantiles/ages take the MAX (the fleet tail is its
        worst replica) — published as serving.ts.fleet_* gauges on the
        group registry next to the per-replica serving.ts.* gauges the
        engines publish themselves. Per-replica summaries ride along
        under `per_replica` so a hot replica is visible next to an idle
        one. Host-side arithmetic only — zero device reads."""
        from deeplearning4j_tpu.telemetry.timeseries import fleet_summary
        summaries = []
        for engine in self.engines:
            if engine.timeseries is not None:
                with engine._lock:
                    summaries.append(engine.timeseries.summary())
        fleet = fleet_summary(summaries)
        for key in ("tokens_per_s", "retirements_per_s",
                    "preemptions_per_s", "queue_depth", "oldest_wait_s",
                    "ttft_p99_s", "tpot_p99_s"):
            if key in fleet:
                self.metrics.gauge(
                    f"serving.ts.fleet_{key}", "fleet-merged windowed "
                    "time-series reading (ISSUE 19)").set(fleet[key])
        fleet["per_replica"] = summaries
        return fleet

    def blame_report(self, results, slo=None, top: int = 3
                     ) -> Dict[str, object]:
        """Fleet blame report (ISSUE 14): run the blame ledger over the
        given finished results/outcomes (from `generate`, a loadgen run,
        or flight-recorder records), join the SLO evaluator's violator
        set, and publish the violators-vs-attainers and per-cohort cause
        breakdowns as serving.blame.* gauges on the group registry.

        Iteration ids in the timelines are process-globally unique, so
        interference edges never pair requests from different replicas
        even though the ledger sees the whole fleet at once. Host-side
        arithmetic over timestamps the engines already took — zero
        device syncs."""
        from deeplearning4j_tpu.telemetry import blame as _blame
        report = _blame.blame_report(results, slo=slo, top=top)
        _blame.publish(report, self.metrics)
        return report
