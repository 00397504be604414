"""Bench artifact schema gate (ISSUE 6 satellite).

bench.py validates the dict it prints; this test validates the validator.
"""
import copy

import pytest

from deeplearning4j_tpu.telemetry.blame import CAUSES as _CAUSES
from deeplearning4j_tpu.util.bench_schema import (assert_valid,
                                                  validate_artifact)


def _minimal_art():
    return {
        "metric": "m", "value": 2000.0, "unit": "images/sec",
        "vs_baseline": None,
        "extra": {
            "resnet50_bf16": {"images_per_sec": 2000.0, "ms_per_iter": 1.0,
                              "platform": "tpu"},
            "decode_serving": {"platform": "cpu", "skipped": True,
                               "skipped_reason": "no TPU"},
            "decode_serving_k1": {"platform": "cpu", "skipped": True,
                                  "skipped_reason": "no TPU"},
            "decode_prefix_share": {
                "platform": "cpu", "prefill_positions_saved": 144,
                "prefill_flops_saved_per_sharer": 4.5e6,
                "kv_bytes_saved": 73728, "ttft_sharer_delta_ms": 0.1,
                "admission_capacity": {"resident_seqs_max": 4,
                                       "slot_equivalent_ceiling": 2}},
            "serving_slo": {
                "platform": "cpu", "seed": 0, "offered_rate": 200.0,
                "goodput": 100.0, "ttft_p99_s": 0.05,
                "slo_attained_frac": 0.8,
                "attainment": [
                    {"offered_rate": 50.0, "goodput": 50.0,
                     "slo_attained_frac": 1.0},
                    {"offered_rate": 100.0, "goodput": 95.0,
                     "slo_attained_frac": 0.95},
                    {"offered_rate": 200.0, "goodput": 100.0,
                     "slo_attained_frac": 0.8}]},
            "serving_chunked_prefill": {
                "platform": "cpu", "chunk_budget": 128,
                "off": {"goodput": 50.0, "ttft_p99_s": 0.05,
                        "slo_attained_frac": 1.0, "prefill_chunks": 0},
                "on": {"goodput": 55.0, "ttft_p99_s": 0.04,
                       "slo_attained_frac": 1.0, "prefill_chunks": 64},
                "deltas": {"ttft_p99_delta_ms": 10.0,
                           "tpot_p99_delta_ms": 1.0,
                           "decode_stall_p99_delta_ms": 2.0,
                           "queue_wait_share_delta": 0.05,
                           "max_sustainable_rate_delta": 0.0}},
            "serving_sharded": {
                "platform": "cpu", "seed": 0, "goodput": 18.0,
                "tp_parity": {"tokens_match": True,
                              "kv_bytes_per_pos_per_chip_ratio": 0.5},
                "replica_ab": {"one_replica": {"goodput": 18.0},
                               "two_replicas": {"goodput": 19.0}}},
            "serving_spec_decode": {
                "platform": "cpu", "spec_draft": 4,
                "tokens_identical": True, "accept_rate": 0.62,
                "tokens_per_sec_on": 120.0, "tokens_per_sec_off": 80.0,
                "tokens_per_sec_delta_frac": 0.5,
                "host_syncs_per_token_on": 0.55,
                "host_syncs_per_token_off": 1.02},
            "kv_observatory": {
                "platform": "cpu", "conserved_every_step": True,
                "sync_parity": True, "rejections": 2,
                "example_rejection": {"blocks_needed": 5, "blocks_free": 2,
                                      "blocks_reclaimable": 8,
                                      "shortfall_blocks": 3},
                "dry_run": [{"policy": "lru", "blocks_freed": 3,
                             "satisfies": True}]},
            "kv_lifecycle": {
                "platform": "cpu", "overcommit": 3.0, "kv_blocks": 10,
                "recompute": {"tokens_identical": True,
                              "all_completed": True,
                              "conserved_every_step": True,
                              "preemptions": 160,
                              "evictions_recompute": 160,
                              "evictions_swap": 0},
                "swap": {"tokens_identical": True, "all_completed": True,
                         "conserved_every_step": True, "preemptions": 160,
                         "evictions_recompute": 0, "evictions_swap": 160,
                         "measured_swap_gbps": 0.5,
                         "host_pool_drained": True}},
            "kv_hierarchy": {
                "platform": "cpu", "overcommit": 3.0, "kv_blocks": 10,
                "host_pool_bytes": 1024,
                "async": {"tokens_identical": True, "all_completed": True,
                          "conserved_every_step": True, "preemptions": 32,
                          "evictions_swap": 32, "harvests": 32,
                          "disk_demotions": 32, "disk_promotions": 32,
                          "host_pool_drained": True,
                          "no_stranded_spills": True},
                "sync": {"tokens_identical": True, "all_completed": True,
                         "conserved_every_step": True, "preemptions": 160,
                         "evictions_swap": 160, "harvests": 0,
                         "disk_demotions": 160, "disk_promotions": 160,
                         "host_pool_drained": True,
                         "no_stranded_spills": True},
                "async_vs_sync": {"p99_preempt_swap_io_s_async": 0.62,
                                  "p99_preempt_swap_io_s_sync": 0.67,
                                  "async_p99_reduced": True},
                "quant_spill": {"bytes_per_eviction_float": 10240.0,
                                "bytes_per_eviction_int8": 2640.0,
                                "spill_bytes_ratio": 3.88,
                                "tokens_identical": True},
                "measured_swap_gbps": 0.013},
            "blame_attribution": {
                "platform": "cpu", "conserved": True,
                "tokens_identical": True, "sync_parity": True,
                "interference_edges": 3,
                "cause_totals_s": {c: 0.1 for c in _CAUSES},
                "violators": {"n": 2,
                              "top": [["queue_wait", 1.2],
                                      ["jit_compile", 0.4]]},
                "attainers": {"n": 3,
                              "top": [["decode_compute", 0.3]]}},
            "quantized_kv": {
                "platform": "cpu", "sync_parity": True,
                "tokens_per_sec_quant": 900.0,
                "tokens_per_sec_float": 1000.0,
                "kv_bytes_per_token_quant": 257.0,
                "kv_bytes_per_token_float": 1024.0,
                "kv_pool_bytes_ratio": 0.251,
                "greedy_tokens_diverged": 1,
                "greedy_tokens_total": 128,
                "max_abs_logprob_delta": 0.0024,
                "capacity_probe": {"pool_byte_budget": 36864,
                                   "resident_seqs_max_float": 2,
                                   "resident_seqs_max_quant": 12}},
            "prefix_radix": {
                "platform": "cpu", "token_parity": True,
                "sync_parity": True, "hit_token_frac": 0.77,
                "flops_saved_frac": 0.88, "prefix_hit_tokens": 3120,
                "fork_prefix_hit_tokens": 320},
            "ts_alerts": {
                "platform": "cpu", "conservation": True,
                "tokens_identical": True, "sync_parity": True,
                "overload_alerts_in_burst": 1, "alerts_in_calm": 0,
                "alert_kinds": {"overload": 1, "goodput_regression": 1,
                                "kv_pressure_spiral": 1, "starvation": 0},
                "peak_burn_rate_short": 7.5, "slo_violations": 6,
                "ts_samples": 28, "host_syncs": 36, "short_window": 8},
            "journal_replay": {
                "platform": "cpu", "replay_token_parity": True,
                "alert_parity": True, "divergence_free": True,
                "overhead_frac": 0.0009, "records": 63,
                "journal_bytes": 6357, "host_syncs": 36},
            "serving_disagg_ab": {
                "platform": "cpu", "token_parity": True,
                "different_winners": True,
                "transfer": {"requests": 6, "bytes": 49152,
                             "bytes_per_request": 8192},
                "mixes": {
                    "ttft_heavy": {
                        "winner": "colocated",
                        "colocated": {"goodput": 20.0,
                                      "ttft_p99_s": 0.05},
                        "disagg": {"goodput": 12.0,
                                   "ttft_p99_s": 0.09}},
                    "tpot_heavy": {
                        "winner": "disagg",
                        "colocated": {"goodput": 8.0,
                                      "ttft_p99_s": 0.04},
                        "disagg": {"goodput": 11.0,
                                   "ttft_p99_s": 0.05}}}},
            "roofline_table": [
                {"function": "train_step", "platform": "tpu",
                 "flops": 1e12, "bytes_accessed": 1e9,
                 "mxu_floor_ms": 5.0, "measured_ms": 10.0, "calls": 3,
                 "mfu": 0.5, "x_floor": 2.0},
            ],
        },
    }


def test_minimal_artifact_valid():
    assert validate_artifact(_minimal_art()) == []
    assert_valid(_minimal_art())            # must not raise


def test_missing_top_key_caught():
    art = _minimal_art()
    del art["vs_baseline"]
    assert any("vs_baseline" in e for e in validate_artifact(art))


def test_decode_serving_must_always_exist():
    art = _minimal_art()
    del art["extra"]["decode_serving"]
    errs = validate_artifact(art)
    assert any("decode_serving" in e and "skipped" in e for e in errs)


def test_decode_serving_needs_reason_or_throughput():
    art = _minimal_art()
    art["extra"]["decode_serving"] = {"platform": "cpu"}
    assert any("neither" in e for e in validate_artifact(art))
    # a measured entry is fine without a reason
    art["extra"]["decode_serving"] = {"platform": "tpu",
                                      "decode_tokens_per_sec": 9000.0}
    assert validate_artifact(art) == []
    # an errored entry is exempt (the error IS the record)
    art["extra"]["decode_serving"] = {"error": "ValueError: boom"}
    assert validate_artifact(art) == []


def test_prefix_share_ab_rules():
    """ISSUE 7: the shared-prefix A/B must always exist; a measured entry
    needs the savings fields + the admission-capacity probe; skipped and
    errored entries are exempt."""
    art = _minimal_art()
    del art["extra"]["decode_prefix_share"]
    assert any("decode_prefix_share" in e for e in validate_artifact(art))
    art = _minimal_art()
    del art["extra"]["decode_prefix_share"]["kv_bytes_saved"]
    assert any("kv_bytes_saved" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["decode_prefix_share"]["admission_capacity"] = {}
    assert any("admission_capacity" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["decode_prefix_share"] = {"error": "ValueError: boom"}
    assert validate_artifact(art) == []
    art["extra"]["decode_prefix_share"] = {"platform": "cpu",
                                           "skipped_reason": "why not"}
    assert validate_artifact(art) == []


def test_serving_slo_rules():
    """ISSUE 8: the open-loop SLO entry must always exist; a measured entry
    needs the headline goodput fields, a platform label, a sane attained
    fraction, and a non-empty well-formed attainment curve."""
    art = _minimal_art()
    del art["extra"]["serving_slo"]
    assert any("serving_slo" in e for e in validate_artifact(art))
    art = _minimal_art()
    del art["extra"]["serving_slo"]["goodput"]
    assert any("goodput" in e for e in validate_artifact(art))
    art = _minimal_art()
    del art["extra"]["serving_slo"]["platform"]
    assert any("serving_slo" in e and "platform" in e
               for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["serving_slo"]["slo_attained_frac"] = 1.4
    assert any("outside [0, 1]" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["serving_slo"]["attainment"] = []
    assert any("attainment" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["serving_slo"]["attainment"][1] = {"offered_rate": 1.0}
    assert any("attainment[1]" in e for e in validate_artifact(art))
    # skipped / errored entries are exempt from the measured-field rules
    art = _minimal_art()
    art["extra"]["serving_slo"] = {"platform": "cpu",
                                   "skipped_reason": "why not"}
    assert validate_artifact(art) == []
    art["extra"]["serving_slo"] = {"error": "ValueError: boom"}
    assert validate_artifact(art) == []


def test_chunked_prefill_ab_rules():
    """ISSUE 9: the chunked-prefill A/B must always exist; a measured
    entry needs a positive chunk budget, both A/B sides with the tail
    stats, the delta fields, and an ON side that actually chunked;
    skipped and errored entries are exempt."""
    art = _minimal_art()
    del art["extra"]["serving_chunked_prefill"]
    assert any("serving_chunked_prefill" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["serving_chunked_prefill"]["chunk_budget"] = 0
    assert any("chunk_budget" in e for e in validate_artifact(art))
    art = _minimal_art()
    del art["extra"]["serving_chunked_prefill"]["off"]["goodput"]
    assert any("serving_chunked_prefill.off" in e
               for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["serving_chunked_prefill"]["on"]["prefill_chunks"] = 0
    assert any("never actually chunked" in e for e in validate_artifact(art))
    art = _minimal_art()
    del art["extra"]["serving_chunked_prefill"]["deltas"]
    assert any("deltas" in e for e in validate_artifact(art))
    art = _minimal_art()
    del art["extra"]["serving_chunked_prefill"]["deltas"][
        "decode_stall_p99_delta_ms"]
    assert any("decode_stall_p99_delta_ms" in e
               for e in validate_artifact(art))
    # a null msr delta is legal (bisection may not sustain at any rate)
    art = _minimal_art()
    art["extra"]["serving_chunked_prefill"]["deltas"][
        "max_sustainable_rate_delta"] = None
    assert validate_artifact(art) == []
    art["extra"]["serving_chunked_prefill"]["deltas"][
        "max_sustainable_rate_delta"] = "oops"
    assert any("max_sustainable_rate_delta" in e
               for e in validate_artifact(art))
    # skipped / errored entries are exempt from the measured-field rules
    art = _minimal_art()
    art["extra"]["serving_chunked_prefill"] = {"error": "ValueError: boom"}
    assert validate_artifact(art) == []
    art["extra"]["serving_chunked_prefill"] = {"platform": "cpu",
                                               "skipped_reason": "why not"}
    assert validate_artifact(art) == []


def test_sharded_serving_rules():
    """ISSUE 10: the multi-chip entry must always exist; a measured entry
    needs the fleet goodput, a TP parity block whose tokens_match is True
    (a drifted TP engine must fail the gate, not publish), the per-chip
    KV bytes ratio, and both replica A/B sides; skipped/errored exempt."""
    art = _minimal_art()
    del art["extra"]["serving_sharded"]
    assert any("serving_sharded" in e for e in validate_artifact(art))
    art = _minimal_art()
    del art["extra"]["serving_sharded"]["platform"]
    assert any("serving_sharded" in e and "platform" in e
               for e in validate_artifact(art))
    art = _minimal_art()
    del art["extra"]["serving_sharded"]["goodput"]
    assert any("serving_sharded'].goodput" in e
               for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["serving_sharded"]["tp_parity"]["tokens_match"] = False
    assert any("tokens_match" in e and "drifted" in e
               for e in validate_artifact(art))
    art = _minimal_art()
    del art["extra"]["serving_sharded"]["tp_parity"][
        "kv_bytes_per_pos_per_chip_ratio"]
    assert any("kv_bytes_per_pos_per_chip_ratio" in e
               for e in validate_artifact(art))
    art = _minimal_art()
    del art["extra"]["serving_sharded"]["replica_ab"]["two_replicas"]
    assert any("replica_ab" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["serving_sharded"]["replica_ab"]["one_replica"][
        "goodput"] = "fast"
    assert any("replica_ab" in e for e in validate_artifact(art))
    # skipped / errored entries are exempt from the measured-field rules
    art = _minimal_art()
    art["extra"]["serving_sharded"] = {"error": "RuntimeError: boom"}
    assert validate_artifact(art) == []
    art["extra"]["serving_sharded"] = {
        "platform": "cpu", "skipped_reason": "needs >= 2*tp devices"}
    assert validate_artifact(art) == []


def test_spec_decode_ab_rules():
    """ISSUE 11: the speculative-decoding A/B must always exist; a measured
    entry needs tokens_identical=True (a spec engine that drifts from the
    plain greedy stream must fail the gate, not publish a 'speedup'), an
    accept rate inside [0, 1], and both sides' tokens/sec + syncs/token;
    skipped/errored entries are exempt."""
    art = _minimal_art()
    del art["extra"]["serving_spec_decode"]
    assert any("serving_spec_decode" in e for e in validate_artifact(art))
    art = _minimal_art()
    del art["extra"]["serving_spec_decode"]["platform"]
    assert any("serving_spec_decode" in e and "platform" in e
               for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["serving_spec_decode"]["tokens_identical"] = False
    assert any("tokens_identical" in e and "drifted" in e
               for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["serving_spec_decode"]["accept_rate"] = 1.5
    assert any("accept_rate" in e for e in validate_artifact(art))
    art = _minimal_art()
    del art["extra"]["serving_spec_decode"]["tokens_per_sec_off"]
    assert any("tokens_per_sec_off" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["serving_spec_decode"]["host_syncs_per_token_on"] = "few"
    assert any("host_syncs_per_token_on" in e
               for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["serving_spec_decode"] = {"error": "ValueError: boom"}
    assert validate_artifact(art) == []
    art["extra"]["serving_spec_decode"] = {"platform": "cpu",
                                           "skipped_reason": "why not"}
    assert validate_artifact(art) == []


def test_kv_observatory_rules():
    """ISSUE 12: the forced-exhaustion pressure run must always exist; a
    measured entry must prove the two in-bench assertions held
    (conserved_every_step, sync_parity), record >= 1 rejection with its
    requested-vs-free-vs-reclaimable forensics, and carry a well-formed
    dry-run row per policy; errored/skipped entries are exempt."""
    art = _minimal_art()
    del art["extra"]["kv_observatory"]
    assert any("kv_observatory" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["kv_observatory"]["conserved_every_step"] = False
    assert any("conserved_every_step" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["kv_observatory"]["sync_parity"] = False
    assert any("sync_parity" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["kv_observatory"]["rejections"] = 0
    assert any("rejections" in e for e in validate_artifact(art))
    art = _minimal_art()
    del art["extra"]["kv_observatory"]["example_rejection"]["shortfall_blocks"]
    assert any("example_rejection" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["kv_observatory"]["dry_run"] = []
    assert any("dry_run" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["kv_observatory"]["dry_run"][0]["satisfies"] = "yes"
    assert any("dry_run[0]" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["kv_observatory"] = {"error": "ValueError: boom"}
    assert validate_artifact(art) == []
    art["extra"]["kv_observatory"] = {"platform": "cpu",
                                      "skipped_reason": "why not"}
    assert validate_artifact(art) == []


def test_kv_lifecycle_rules():
    """ISSUE 13: the forced-exhaustion REAL-eviction run must always
    exist; a measured entry must prove parity/completion/conservation
    for BOTH preemption flavors, >= 1 actual preemption per flavor, no
    flavor leakage under the forced modes, and the swap side must carry
    the measured bandwidth + a drained host pool; errored/skipped
    entries are exempt."""
    art = _minimal_art()
    del art["extra"]["kv_lifecycle"]
    assert any("kv_lifecycle" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["kv_lifecycle"]["overcommit"] = 1.5
    assert any("overcommit" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["kv_lifecycle"]["recompute"]["tokens_identical"] = False
    assert any("recompute.tokens_identical" in e
               for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["kv_lifecycle"]["swap"]["preemptions"] = 0
    assert any("swap.preemptions" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["kv_lifecycle"]["recompute"]["evictions_swap"] = 3
    assert any("evictions_swap must be 0" in e
               for e in validate_artifact(art))
    art = _minimal_art()
    del art["extra"]["kv_lifecycle"]["swap"]["measured_swap_gbps"]
    assert any("measured_swap_gbps" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["kv_lifecycle"]["swap"]["host_pool_drained"] = False
    assert any("host_pool_drained" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["kv_lifecycle"] = {"error": "ValueError: boom"}
    assert validate_artifact(art) == []
    art["extra"]["kv_lifecycle"] = {"platform": "cpu",
                                    "skipped_reason": "why not"}
    assert validate_artifact(art) == []


def test_kv_hierarchy_rules():
    """ISSUE 18: the three-tier overcommit run must always exist; a
    measured entry must prove parity/conservation/drained pools for
    BOTH swap pipelines, real disk demotions AND promotions, an async
    side that harvested deferred readbacks and reduced p99 swap blame,
    a >= 3x int8 spill shrink, and a calibrated bandwidth;
    errored/skipped entries are exempt."""
    art = _minimal_art()
    del art["extra"]["kv_hierarchy"]
    assert any("kv_hierarchy" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["kv_hierarchy"]["overcommit"] = 1.5
    assert any("overcommit" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["kv_hierarchy"]["async"]["tokens_identical"] = False
    assert any("async.tokens_identical" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["kv_hierarchy"]["sync"]["disk_demotions"] = 0
    assert any("sync.disk_demotions" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["kv_hierarchy"]["async"]["disk_promotions"] = 0
    assert any("async.disk_promotions" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["kv_hierarchy"]["async"]["harvests"] = 0
    assert any("harvests" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["kv_hierarchy"]["async"]["no_stranded_spills"] = False
    assert any("no_stranded_spills" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["kv_hierarchy"]["async_vs_sync"]["async_p99_reduced"] = False
    assert any("async_p99_reduced" in e for e in validate_artifact(art))
    art = _minimal_art()
    del art["extra"]["kv_hierarchy"]["async_vs_sync"][
        "p99_preempt_swap_io_s_sync"]
    assert any("p99_preempt_swap_io_s_sync" in e
               for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["kv_hierarchy"]["quant_spill"]["spill_bytes_ratio"] = 2.4
    assert any("spill_bytes_ratio" in e for e in validate_artifact(art))
    art = _minimal_art()
    del art["extra"]["kv_hierarchy"]["measured_swap_gbps"]
    assert any("kv_hierarchy.measured_swap_gbps" in e
               for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["kv_hierarchy"] = {"error": "ValueError: boom"}
    assert validate_artifact(art) == []
    art["extra"]["kv_hierarchy"] = {"platform": "cpu",
                                    "skipped_reason": "why not"}
    assert validate_artifact(art) == []


def test_blame_attribution_rules():
    """ISSUE 14: the forced-contention blame run must always exist; a
    measured entry must prove the in-bench assertions held (conservation
    + ledger-on/off token and host-sync parity), have found >= 1
    interference edge, and keep the cause taxonomy closed — cause keys
    come from telemetry/blame.py, never invented in bench output;
    errored/skipped entries are exempt."""
    art = _minimal_art()
    del art["extra"]["blame_attribution"]
    assert any("blame_attribution" in e for e in validate_artifact(art))
    for flag in ("conserved", "tokens_identical", "sync_parity"):
        art = _minimal_art()
        art["extra"]["blame_attribution"][flag] = False
        assert any(f"blame_attribution.{flag}" in e
                   for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["blame_attribution"]["interference_edges"] = 0
    assert any("interference_edges" in e for e in validate_artifact(art))
    # closed taxonomy: a missing cause and an invented cause both fail
    art = _minimal_art()
    del art["extra"]["blame_attribution"]["cause_totals_s"]["queue_wait"]
    assert any("closed cause taxonomy" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["blame_attribution"]["cause_totals_s"]["vibes"] = 1.0
    assert any("closed cause taxonomy" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["blame_attribution"]["cause_totals_s"]["queue_wait"] = -1.0
    assert any("non-negative" in e for e in validate_artifact(art))
    # the rendered top tables must reference taxonomy causes only
    art = _minimal_art()
    art["extra"]["blame_attribution"]["violators"]["top"] = [["vibes", 1.0]]
    assert any("violators.top[0]" in e for e in validate_artifact(art))
    art = _minimal_art()
    del art["extra"]["blame_attribution"]["attainers"]
    assert any("attainers" in e for e in validate_artifact(art))
    # errored/skipped runs are exempt from the measured-entry rules
    art = _minimal_art()
    art["extra"]["blame_attribution"] = {"error": "ValueError: boom"}
    assert validate_artifact(art) == []
    art["extra"]["blame_attribution"] = {"platform": "cpu",
                                         "skipped_reason": "why not"}
    assert validate_artifact(art) == []


def test_quantized_kv_rules():
    """ISSUE 15: the quantized-KV A/B must always exist; a measured entry
    must prove the in-bench sync-parity assertion held, carry accuracy
    next to throughput (divergence under the disclosed 2% gate), show a
    real pool shrink (< 0.5 of the float pool), and a byte-equal
    capacity probe where quant holds >= as many resident sequences;
    errored/skipped entries are exempt."""
    art = _minimal_art()
    del art["extra"]["quantized_kv"]
    assert any("quantized_kv" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["quantized_kv"]["sync_parity"] = False
    assert any("sync_parity" in e for e in validate_artifact(art))
    # a dequantized copy (ratio >= 0.5) fails the gate
    art = _minimal_art()
    art["extra"]["quantized_kv"]["kv_pool_bytes_ratio"] = 0.75
    assert any("kv_pool_bytes_ratio" in e for e in validate_artifact(art))
    # divergence above the disclosed 2% gate fails
    art = _minimal_art()
    art["extra"]["quantized_kv"]["greedy_tokens_diverged"] = 50
    assert any("divergence" in e for e in validate_artifact(art))
    # accuracy numbers cannot be dropped
    art = _minimal_art()
    del art["extra"]["quantized_kv"]["max_abs_logprob_delta"]
    assert any("max_abs_logprob_delta" in e for e in validate_artifact(art))
    # capacity probe must exist and must not show quant holding FEWER
    art = _minimal_art()
    del art["extra"]["quantized_kv"]["capacity_probe"]
    assert any("capacity_probe" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["quantized_kv"]["capacity_probe"][
        "resident_seqs_max_quant"] = 1
    assert any("FEWER" in e for e in validate_artifact(art))
    # errored/skipped runs are exempt
    art = _minimal_art()
    art["extra"]["quantized_kv"] = {"error": "ValueError: boom"}
    assert validate_artifact(art) == []
    art["extra"]["quantized_kv"] = {"platform": "cpu",
                                    "skipped_reason": "why not"}
    assert validate_artifact(art) == []


def test_prefix_radix_rules():
    """ISSUE 16: the radix prefix-cache A/B must always exist; a measured
    entry must prove BOTH in-bench parity assertions held (greedy tokens
    and host-sync counts), carry sane fractions, and show the fork
    branch actually shared pre-fork history; errored/skipped exempt."""
    art = _minimal_art()
    del art["extra"]["prefix_radix"]
    assert any("prefix_radix" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["prefix_radix"]["token_parity"] = False
    assert any("token_parity" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["prefix_radix"]["sync_parity"] = False
    assert any("sync_parity" in e for e in validate_artifact(art))
    for frac_key in ("hit_token_frac", "flops_saved_frac"):
        art = _minimal_art()
        art["extra"]["prefix_radix"][frac_key] = 1.2
        assert any(frac_key in e for e in validate_artifact(art))
        art = _minimal_art()
        del art["extra"]["prefix_radix"][frac_key]
        assert any(frac_key in e for e in validate_artifact(art))
    # a fork that shared nothing means the radix tree didn't do its job
    art = _minimal_art()
    art["extra"]["prefix_radix"]["fork_prefix_hit_tokens"] = 0
    assert any("fork" in e for e in validate_artifact(art))
    # errored/skipped runs are exempt
    art = _minimal_art()
    art["extra"]["prefix_radix"] = {"error": "ValueError: boom"}
    assert validate_artifact(art) == []
    art["extra"]["prefix_radix"] = {"platform": "cpu",
                                    "skipped_reason": "why not"}
    assert validate_artifact(art) == []


def test_ts_alerts_rules():
    """ISSUE 19: the forced-overload alert run must always exist; a
    measured entry must prove the in-bench assertions held (>= 1
    overload page inside the burst, zero calm-phase alerts, windowed
    conservation, on/off token + host-sync parity) and keep the alert
    taxonomy closed — kinds come from telemetry/alerts.py ALERT_KINDS,
    never invented in bench output; errored/skipped exempt."""
    art = _minimal_art()
    del art["extra"]["ts_alerts"]
    assert any("ts_alerts" in e for e in validate_artifact(art))
    for flag in ("conservation", "tokens_identical", "sync_parity"):
        art = _minimal_art()
        art["extra"]["ts_alerts"][flag] = False
        assert any(f"ts_alerts.{flag}" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["ts_alerts"]["overload_alerts_in_burst"] = 0
    assert any("never paged" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["ts_alerts"]["alerts_in_calm"] = 2
    assert any("calm" in e for e in validate_artifact(art))
    # closed taxonomy: a missing kind and an invented kind both fail
    art = _minimal_art()
    del art["extra"]["ts_alerts"]["alert_kinds"]["starvation"]
    assert any("closed alert taxonomy" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["ts_alerts"]["alert_kinds"]["vibes"] = 1
    assert any("closed alert taxonomy" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["ts_alerts"]["alert_kinds"]["overload"] = -1
    assert any("non-negative" in e for e in validate_artifact(art))
    art = _minimal_art()
    del art["extra"]["ts_alerts"]["peak_burn_rate_short"]
    assert any("peak_burn_rate_short" in e for e in validate_artifact(art))
    # errored/skipped runs are exempt
    art = _minimal_art()
    art["extra"]["ts_alerts"] = {"error": "ValueError: boom"}
    assert validate_artifact(art) == []
    art["extra"]["ts_alerts"] = {"platform": "cpu",
                                 "skipped_reason": "why not"}
    assert validate_artifact(art) == []


def test_journal_replay_rules():
    """ISSUE 20: the record/replay round-trip must always exist; a
    measured entry must prove the in-bench assertions held (replayed
    token parity, deterministic-alert parity, divergence localizer
    None) and the <1% journal-overhead bound; errored/skipped exempt."""
    art = _minimal_art()
    del art["extra"]["journal_replay"]
    assert any("journal_replay" in e for e in validate_artifact(art))
    for flag in ("replay_token_parity", "alert_parity",
                 "divergence_free"):
        art = _minimal_art()
        art["extra"]["journal_replay"][flag] = False
        assert any(f"journal_replay.{flag}" in e
                   for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["journal_replay"]["overhead_frac"] = 0.02
    assert any("overhead_frac" in e for e in validate_artifact(art))
    art = _minimal_art()
    del art["extra"]["journal_replay"]["overhead_frac"]
    assert any("overhead_frac" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["journal_replay"]["records"] = 0
    assert any("journaled nothing" in e for e in validate_artifact(art))
    # errored/skipped runs are exempt
    art = _minimal_art()
    art["extra"]["journal_replay"] = {"error": "ValueError: boom"}
    assert validate_artifact(art) == []
    art["extra"]["journal_replay"] = {"platform": "cpu",
                                      "skipped_reason": "why not"}
    assert validate_artifact(art) == []


def test_serving_disagg_ab_rules():
    """ISSUE 17: the disagg A/B must always exist; a measured entry must
    prove token parity held, state the different-winners headline as an
    explicit boolean (an honest False beats a dropped mix), carry BOTH
    mixes with per-side goodput/TTFT and a winner each, and show KV
    actually migrated; errored/skipped exempt."""
    art = _minimal_art()
    del art["extra"]["serving_disagg_ab"]
    assert any("serving_disagg_ab" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["serving_disagg_ab"]["token_parity"] = False
    assert any("token_parity" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["serving_disagg_ab"]["different_winners"] = "yes"
    assert any("different_winners" in e for e in validate_artifact(art))
    for mix in ("ttft_heavy", "tpot_heavy"):
        art = _minimal_art()
        del art["extra"]["serving_disagg_ab"]["mixes"][mix]
        assert any(mix in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["serving_disagg_ab"]["mixes"]["ttft_heavy"]["winner"] = \
        "both"
    assert any("winner" in e for e in validate_artifact(art))
    art = _minimal_art()
    del art["extra"]["serving_disagg_ab"]["mixes"]["tpot_heavy"][
        "disagg"]["goodput"]
    assert any("tpot_heavy" in e and "goodput" in e
               for e in validate_artifact(art))
    # zero transferred bytes means the disagg side never disaggregated
    art = _minimal_art()
    art["extra"]["serving_disagg_ab"]["transfer"]["bytes"] = 0
    assert any("transfer" in e for e in validate_artifact(art))
    # errored/skipped runs are exempt
    art = _minimal_art()
    art["extra"]["serving_disagg_ab"] = {"error": "ValueError: boom"}
    assert validate_artifact(art) == []
    art["extra"]["serving_disagg_ab"] = {"platform": "cpu",
                                         "skipped_reason": "1 device"}
    assert validate_artifact(art) == []


def test_goodput_dict_is_a_measurement_needing_platform():
    art = _minimal_art()
    art["extra"]["some_slo_thing"] = {"goodput": 5.0}
    assert any("some_slo_thing" in e and "platform" in e
               for e in validate_artifact(art))


def test_measurement_dict_requires_platform_label():
    art = _minimal_art()
    del art["extra"]["resnet50_bf16"]["platform"]
    errs = validate_artifact(art)
    assert any("resnet50_bf16" in e and "platform" in e for e in errs)
    # non-measurement dicts (notes, rooflines) need no label
    art = _minimal_art()
    art["extra"]["some_note"] = {"verdict": "fine"}
    assert validate_artifact(art) == []


def test_roofline_row_validation():
    art = _minimal_art()
    row = art["extra"]["roofline_table"][0]
    row["mfu"] = 1.6                         # past peak: impossible
    assert any("mfu" in e for e in validate_artifact(art))
    row["mfu"] = 2.9e-10                     # tiny CPU row: legal
    assert validate_artifact(art) == []
    row["mfu"] = None                        # unmeasured: legal
    assert validate_artifact(art) == []
    del row["function"]
    assert any("function" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["roofline_table"][0]["measured_ms"] = -1.0
    assert any("measured_ms" in e for e in validate_artifact(art))
    art = _minimal_art()
    art["extra"]["roofline_table"] = "oops"
    assert any("not a list" in e for e in validate_artifact(art))


def test_assert_valid_raises_with_all_violations():
    art = _minimal_art()
    del art["extra"]["decode_serving"]
    del art["extra"]["resnet50_bf16"]["platform"]
    with pytest.raises(AssertionError) as ei:
        assert_valid(art)
    msg = str(ei.value)
    assert "decode_serving" in msg and "resnet50_bf16" in msg
