"""Schema gate for the bench artifact (ISSUE 6 satellite).

The JSON line `bench.py` prints is what a reader of a run sees, so a
malformed artifact silently becomes malformed numbers.
`validate_artifact` checks the structural contract — and the ISSUE 6
additions: every measured entry carries a `platform`
label, `decode_serving`/`decode_serving_k1` are ALWAYS present (skipped
runs say so via `skipped_reason` instead of vanishing), and the
auto-generated `roofline_table` rows are well-formed. ISSUE 7 adds
`decode_prefix_share` (the shared-prefix A/B — CPU-runnable, so it is
always present and, when measured, must carry the savings fields the
docs render). ISSUE 8 adds `serving_slo` (the open-loop goodput/SLO
observatory — also CPU-runnable and always present; measured entries
must carry offered_rate/goodput/ttft_p99_s/slo_attained_frac/seed/
platform plus a well-formed attainment curve). ISSUE 9 adds
`serving_chunked_prefill` (the chunked-prefill A/B — CPU-runnable and
always present; measured entries must carry a numeric chunk_budget,
off/on sides with the tail stats the docs render, and the delta
fields). ISSUE 10 adds `serving_sharded` (the multi-chip TP parity +
replica goodput A/B — always present; measured entries must carry the
fleet `goodput`, a `tp_parity` block whose tokens_match is True, and a
`replica_ab` block with both sides' goodput). ISSUE 11 adds
`serving_spec_decode` (the speculative-decoding A/B — CPU-runnable and
always present; measured entries must carry tokens_identical=True, an
accept_rate in [0, 1], and both sides' tokens/sec and syncs/token).
ISSUE 12 adds `kv_observatory` (the forced-exhaustion pressure run —
CPU-runnable and always present; measured entries must prove both
in-bench assertions held: conserved_every_step=True and
sync_parity=True, carry >= 1 recorded rejection with its
requested-vs-free-vs-reclaimable forensics, and a well-formed dry-run
row per eviction policy). ISSUE 13 adds `kv_lifecycle` (the
forced-exhaustion REAL-eviction run — CPU-runnable and always present;
measured entries must prove token parity + completion + conservation
for both preemption flavors, >= 1 actual preemption per flavor, no
flavor leakage under forced modes, and a measured swap bandwidth).
ISSUE 14 adds `blame_attribution` (the latency blame ledger under
forced contention — CPU-runnable and always present; measured entries
must prove the in-bench assertions held: conserved=True,
tokens_identical=True and sync_parity=True for the ledger-on/off A/B,
>= 1 interference edge, and cause_totals_s keyed by EXACTLY the closed
cause taxonomy telemetry/blame.py defines). ISSUE 15 adds
`quantized_kv` (the int8-KV + weight-only-int8 A/B — CPU-runnable and
always present; measured entries must prove sync_parity=True, carry
throughput NEXT TO its accuracy cost — divergence count under the
disclosed 2% gate plus max_abs_logprob_delta — a pool-byte ratio in
(0, 0.5), and a byte-equal capacity probe where the quantized pool
holds at least as many resident sequences). ISSUE 16 adds
`prefix_radix` (the radix-tree prefix cache A/B on a seeded
multi-turn/fork session mix — CPU-runnable and always present;
measured entries must prove token_parity=True AND sync_parity=True, a
hit_token_frac and flops_saved_frac in [0, 1], and
fork_prefix_hit_tokens > 0). ISSUE 17 adds `serving_disagg_ab` (the
disaggregated prefill/decode A/B on the same seeded schedules —
CPU-runnable and always present; measured entries must prove
token_parity=True, carry BOTH mixes (ttft_heavy + tpot_heavy) with
colocated/disagg sides and a winner each, a boolean different_winners
headline — reported honestly whichever way it lands — and a transfer
block with positive migrated bytes, else the disagg side never
actually disaggregated). ISSUE 18 adds `kv_hierarchy` (the three-tier
HBM→host→disk overcommit run — CPU-runnable and always present;
measured entries must prove token parity + conservation + drained
pools for BOTH swap pipelines, real disk demotions AND promotions,
an async pipeline that harvested >= 1 deferred readback and reduced
p99 preempt_swap_io blame vs sync, a >= 3x int8 spill-byte shrink,
and a calibrated swap bandwidth). ISSUE 19 adds `ts_alerts` (the
forced-overload alert-discrimination run — CPU-runnable and always
present; measured entries must prove >= 1 overload page stamped inside
the burst phase, alerts_in_calm == 0, windowed-delta conservation,
ts+alerts on/off token + host-sync bit-parity, and an alert_kinds dict
keyed by EXACTLY the closed taxonomy telemetry/alerts.py defines).
ISSUE 20 adds `journal_replay` (the decision-journal record/replay
round-trip on the same forced-overload schedule — CPU-runnable and
always present; measured entries must prove bit-identical replayed
tokens, deterministic-alert-count parity, a None divergence localizer,
and journal overhead under 1% of the recorded wall).
bench.py calls `assert_valid` on the dict it is about to print;
tests/test_bench_schema.py validates the validator. `vs_baseline` is null:
no baseline was measured on today's installation.
"""
from __future__ import annotations

from typing import List

TOP_KEYS = ("metric", "value", "unit", "vs_baseline", "extra")

# extra[] entries that are measurement dicts and must carry `platform`
# (ISSUE 6 satellite: a CPU-measured ms must never read as a TPU claim).
# Any dict entry holding one of these keys counts as a measurement.
_MEASUREMENT_KEYS = ("images_per_sec", "tokens_per_sec", "samples_per_sec",
                     "ms_per_iter", "decode_tokens_per_sec",
                     "ms_per_iter_health_on", "goodput")

_ROOFLINE_ROW_REQ = ("function", "platform", "flops", "mxu_floor_ms",
                     "measured_ms", "calls")


def _is_num(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def validate_artifact(art: dict) -> List[str]:
    """Return a list of human-readable schema violations (empty = valid)."""
    errs: List[str] = []
    if not isinstance(art, dict):
        return ["artifact is not a dict"]
    for k in TOP_KEYS:
        if k not in art:
            errs.append(f"missing top-level key '{k}'")
    if errs:
        return errs
    if not _is_num(art["value"]):
        errs.append("'value' is not a number")
    if art["vs_baseline"] is not None:
        errs.append("'vs_baseline' is not null (no baseline has been "
                    "measured on this installation)")
    if not isinstance(art["unit"], str) or not art["unit"]:
        errs.append("'unit' is not a non-empty string")
    e = art["extra"]
    if not isinstance(e, dict):
        return errs + ["'extra' is not a dict"]

    # decode_serving must ALWAYS exist: measured (decode_tokens_per_sec),
    # skipped (skipped_reason), or errored (error) — never absent.
    for key in ("decode_serving", "decode_serving_k1"):
        d = e.get(key)
        if not isinstance(d, dict):
            errs.append(f"extra['{key}'] missing or not a dict "
                        "(skipped runs must still emit it)")
            continue
        if "error" in d:
            continue
        if "platform" not in d:
            errs.append(f"extra['{key}'] has no 'platform' label")
        if "decode_tokens_per_sec" not in d and "skipped_reason" not in d:
            errs.append(f"extra['{key}'] has neither decode_tokens_per_sec "
                        "nor skipped_reason")

    # shared-prefix A/B (ISSUE 7): CPU-runnable, so it must always exist;
    # when measured it must carry the savings fields the docs render plus
    # the admission-capacity probe
    ps = e.get("decode_prefix_share")
    if not isinstance(ps, dict):
        errs.append("extra['decode_prefix_share'] missing or not a dict "
                    "(the A/B runs on any platform — emit error/skipped "
                    "entries rather than dropping it)")
    elif "error" not in ps and "skipped_reason" not in ps:
        if "platform" not in ps:
            errs.append("extra['decode_prefix_share'] has no 'platform' "
                        "label")
        for k in ("prefill_positions_saved", "prefill_flops_saved_per_sharer",
                  "kv_bytes_saved", "ttft_sharer_delta_ms"):
            if not _is_num(ps.get(k)):
                errs.append(f"extra['decode_prefix_share'].{k} missing or "
                            "not a number")
        cap = ps.get("admission_capacity")
        if not isinstance(cap, dict) or not all(
                _is_num(cap.get(k)) for k in ("resident_seqs_max",
                                              "slot_equivalent_ceiling")):
            errs.append("extra['decode_prefix_share'].admission_capacity "
                        "must carry numeric resident_seqs_max and "
                        "slot_equivalent_ceiling")

    # serving SLO observatory (ISSUE 8): the reduced-config open-loop run
    # is CPU-runnable, so the entry must always exist; when measured it
    # must carry the headline goodput fields plus an attainment curve of
    # well-formed rate points (the docs render both)
    ss = e.get("serving_slo")
    if not isinstance(ss, dict):
        errs.append("extra['serving_slo'] missing or not a dict (the "
                    "open-loop SLO bench runs on any platform — emit "
                    "error/skipped entries rather than dropping it)")
    elif "error" not in ss and "skipped_reason" not in ss:
        for k in ("offered_rate", "goodput", "ttft_p99_s",
                  "slo_attained_frac", "seed"):
            if not _is_num(ss.get(k)):
                errs.append(f"extra['serving_slo'].{k} missing or not a "
                            "number")
        if not isinstance(ss.get("platform"), str):
            errs.append("extra['serving_slo'] has no 'platform' label")
        frac = ss.get("slo_attained_frac")
        if _is_num(frac) and not 0 <= frac <= 1:
            errs.append(f"extra['serving_slo'].slo_attained_frac {frac!r} "
                        "outside [0, 1]")
        curve = ss.get("attainment")
        if not isinstance(curve, list) or not curve:
            errs.append("extra['serving_slo'].attainment missing or empty "
                        "(goodput-vs-offered-load curve)")
        else:
            for i, row in enumerate(curve):
                if not isinstance(row, dict) or not all(
                        _is_num(row.get(k)) for k in
                        ("offered_rate", "goodput", "slo_attained_frac")):
                    errs.append(f"serving_slo.attainment[{i}] must carry "
                                "numeric offered_rate/goodput/"
                                "slo_attained_frac")

    # chunked-prefill A/B (ISSUE 9): CPU-runnable and always present;
    # when measured it must carry the chunk budget, both sides of the
    # A/B with the tail stats the docs render, and the delta fields
    cp = e.get("serving_chunked_prefill")
    if not isinstance(cp, dict):
        errs.append("extra['serving_chunked_prefill'] missing or not a "
                    "dict (the A/B runs on any platform — emit error/"
                    "skipped entries rather than dropping it)")
    elif "error" not in cp and "skipped_reason" not in cp:
        if not isinstance(cp.get("platform"), str):
            errs.append("extra['serving_chunked_prefill'] has no "
                        "'platform' label")
        if not _is_num(cp.get("chunk_budget")) or cp.get("chunk_budget", 0) \
                <= 0:
            errs.append("extra['serving_chunked_prefill'].chunk_budget "
                        "missing or not a positive number")
        for side in ("off", "on"):
            s = cp.get(side)
            if not isinstance(s, dict) or not all(
                    _is_num(s.get(k)) for k in
                    ("goodput", "ttft_p99_s", "slo_attained_frac")):
                errs.append(f"serving_chunked_prefill.{side} must carry "
                            "numeric goodput/ttft_p99_s/slo_attained_frac")
        on = cp.get("on")
        if isinstance(on, dict) and on.get("prefill_chunks", 1) == 0:
            errs.append("serving_chunked_prefill.on ran zero prefill "
                        "chunks — the ON side never actually chunked")
        d = cp.get("deltas")
        if not isinstance(d, dict):
            errs.append("extra['serving_chunked_prefill'].deltas missing "
                        "or not a dict")
        else:
            for k in ("ttft_p99_delta_ms", "tpot_p99_delta_ms",
                      "decode_stall_p99_delta_ms"):
                if not _is_num(d.get(k)):
                    errs.append(f"serving_chunked_prefill.deltas.{k} "
                                "missing or not a number")
            # msr comes from a coarse bisection and may legitimately be
            # None (never sustained at any probed rate on either side)
            msr = d.get("max_sustainable_rate_delta")
            if msr is not None and not _is_num(msr):
                errs.append("serving_chunked_prefill.deltas."
                            "max_sustainable_rate_delta must be numeric "
                            "or null")

    # multi-chip sharded serving (ISSUE 10): runs on forced host devices,
    # so the entry must always exist; when measured the TP side must have
    # actually matched tokens (a sharded engine that drifts is a bug, not
    # a data point) and both replica-A/B sides must carry goodput
    sh = e.get("serving_sharded")
    if not isinstance(sh, dict):
        errs.append("extra['serving_sharded'] missing or not a dict (the "
                    "sharded bench runs on forced host devices — emit "
                    "error/skipped entries rather than dropping it)")
    elif "error" not in sh and "skipped_reason" not in sh:
        if not isinstance(sh.get("platform"), str):
            errs.append("extra['serving_sharded'] has no 'platform' label")
        if not _is_num(sh.get("goodput")):
            errs.append("extra['serving_sharded'].goodput missing or not "
                        "a number")
        tpp = sh.get("tp_parity")
        if not isinstance(tpp, dict) or tpp.get("tokens_match") is not True:
            errs.append("serving_sharded.tp_parity.tokens_match must be "
                        "True — the TP engine drifted from the single-chip "
                        "token stream")
        elif not _is_num(tpp.get("kv_bytes_per_pos_per_chip_ratio")):
            errs.append("serving_sharded.tp_parity."
                        "kv_bytes_per_pos_per_chip_ratio missing or not a "
                        "number")
        ab = sh.get("replica_ab")
        if not isinstance(ab, dict) or not all(
                isinstance(ab.get(s), dict)
                and _is_num(ab[s].get("goodput"))
                for s in ("one_replica", "two_replicas")):
            errs.append("serving_sharded.replica_ab must carry "
                        "one_replica/two_replicas dicts with numeric "
                        "goodput")

    # speculative-decode A/B (ISSUE 11): CPU-runnable, so always present;
    # when measured the greedy token streams MUST have matched (a
    # faster-but-different decode is a bug, not a win) and the accept
    # rate must be a sane fraction
    sp = e.get("serving_spec_decode")
    if not isinstance(sp, dict):
        errs.append("extra['serving_spec_decode'] missing or not a dict "
                    "(the spec-decode A/B is CPU-runnable — emit "
                    "error/skipped entries rather than dropping it)")
    elif "error" not in sp and "skipped_reason" not in sp:
        if not isinstance(sp.get("platform"), str):
            errs.append("extra['serving_spec_decode'] has no 'platform' "
                        "label")
        if sp.get("tokens_identical") is not True:
            errs.append("serving_spec_decode.tokens_identical must be True "
                        "— speculative decode drifted from the plain "
                        "greedy token stream")
        ar = sp.get("accept_rate")
        if not _is_num(ar) or not 0.0 <= ar <= 1.0:
            errs.append("serving_spec_decode.accept_rate missing or "
                        "outside [0, 1]")
        for k in ("tokens_per_sec_on", "tokens_per_sec_off",
                  "host_syncs_per_token_on", "host_syncs_per_token_off"):
            if not _is_num(sp.get(k)):
                errs.append(f"serving_spec_decode.{k} missing or not a "
                            "number")

    # KV-pressure observatory (ISSUE 12): CPU-runnable forced-exhaustion
    # run, so always present; when measured the two in-bench assertions
    # must have held (conservation every iteration, sync bit-parity
    # on-vs-off), at least one rejection must have been recorded (else
    # the forensics path never executed), and every dry-run policy row
    # must be well-formed — the docs render the ranked victims
    ko = e.get("kv_observatory")
    if not isinstance(ko, dict):
        errs.append("extra['kv_observatory'] missing or not a dict (the "
                    "forced-exhaustion run is CPU-runnable — emit error/"
                    "skipped entries rather than dropping it)")
    elif "error" not in ko and "skipped_reason" not in ko:
        if not isinstance(ko.get("platform"), str):
            errs.append("extra['kv_observatory'] has no 'platform' label")
        if ko.get("conserved_every_step") is not True:
            errs.append("kv_observatory.conserved_every_step must be True "
                        "— the byte partition drifted from the pool size")
        if ko.get("sync_parity") is not True:
            errs.append("kv_observatory.sync_parity must be True — the "
                        "observatory added device syncs")
        if not _is_num(ko.get("rejections")) or ko.get("rejections", 0) < 1:
            errs.append("kv_observatory.rejections missing or < 1 — the "
                        "forced-exhaustion workload never exercised the "
                        "forensics path")
        ex = ko.get("example_rejection")
        if not isinstance(ex, dict) or not all(
                _is_num(ex.get(k)) for k in
                ("blocks_needed", "blocks_free", "blocks_reclaimable",
                 "shortfall_blocks")):
            errs.append("kv_observatory.example_rejection must carry "
                        "numeric blocks_needed/blocks_free/"
                        "blocks_reclaimable/shortfall_blocks")
        dr = ko.get("dry_run")
        if not isinstance(dr, list) or not dr:
            errs.append("kv_observatory.dry_run missing or empty (one row "
                        "per eviction policy)")
        else:
            for i, row in enumerate(dr):
                if not isinstance(row, dict) \
                        or not isinstance(row.get("policy"), str) \
                        or not _is_num(row.get("blocks_freed")) \
                        or not isinstance(row.get("satisfies"), bool):
                    errs.append(f"kv_observatory.dry_run[{i}] must carry "
                                "policy (str), blocks_freed (num), "
                                "satisfies (bool)")

    # KV lifecycle manager (ISSUE 13): CPU-runnable forced-exhaustion
    # eviction run, so always present; when measured BOTH preemption
    # flavors must prove the in-bench assertions held (token parity vs
    # the never-evicted reference, all requests completed, conservation
    # every iteration), each flavor must have actually preempted, the
    # counters must name the right flavor, and the swap side must carry
    # the measured host round-trip bandwidth PERF.md's cost model cites
    kl = e.get("kv_lifecycle")
    if not isinstance(kl, dict):
        errs.append("extra['kv_lifecycle'] missing or not a dict (the "
                    "forced-exhaustion eviction run is CPU-runnable — "
                    "emit error/skipped entries rather than dropping it)")
    elif "error" not in kl and "skipped_reason" not in kl:
        if not isinstance(kl.get("platform"), str):
            errs.append("extra['kv_lifecycle'] has no 'platform' label")
        if not _is_num(kl.get("overcommit")) or kl.get("overcommit", 0) < 2:
            errs.append("kv_lifecycle.overcommit missing or < 2 — the "
                        "workload never forced real pool exhaustion")
        for mode in ("recompute", "swap"):
            row = kl.get(mode)
            if not isinstance(row, dict):
                errs.append(f"kv_lifecycle.{mode} missing or not a dict")
                continue
            for flag in ("tokens_identical", "all_completed",
                         "conserved_every_step"):
                if row.get(flag) is not True:
                    errs.append(f"kv_lifecycle.{mode}.{flag} must be True")
            if not _is_num(row.get("preemptions")) \
                    or row.get("preemptions", 0) < 1:
                errs.append(f"kv_lifecycle.{mode}.preemptions missing or "
                            "< 1 — no eviction actually happened")
            wrong = ("evictions_swap" if mode == "recompute"
                     else "evictions_recompute")
            if row.get(wrong, 0) != 0:
                errs.append(f"kv_lifecycle.{mode}.{wrong} must be 0 — the "
                            "forced mode leaked the other flavor")
        swap = kl.get("swap")
        if isinstance(swap, dict) and "error" not in kl:
            if not _is_num(swap.get("measured_swap_gbps")):
                errs.append("kv_lifecycle.swap.measured_swap_gbps missing "
                            "or not a number — no swap round-trip was "
                            "timed")
            if swap.get("host_pool_drained") is not True:
                errs.append("kv_lifecycle.swap.host_pool_drained must be "
                            "True — swapped blocks leaked in host RAM")

    # Hierarchical KV storage (ISSUE 18): CPU-runnable three-tier
    # overcommit run, so always present; when measured BOTH swap
    # pipelines (async and sync) must prove the in-bench assertions held
    # (token parity vs the never-evicted reference, completion,
    # conservation every iteration, drained pools, zero stranded spill
    # files), both must have actually demoted to AND promoted from the
    # disk tier (else the host-pool cap never forced the third tier),
    # the async side must have harvested >= 1 deferred readback and
    # REDUCED p99 preempt_swap_io blame vs sync on the same schedule,
    # and the int8 spill must move >= 3x fewer bytes per eviction than
    # float through the same ladder
    kh = e.get("kv_hierarchy")
    if not isinstance(kh, dict):
        errs.append("extra['kv_hierarchy'] missing or not a dict (the "
                    "three-tier overcommit run is CPU-runnable — emit "
                    "error/skipped entries rather than dropping it)")
    elif "error" not in kh and "skipped_reason" not in kh:
        if not isinstance(kh.get("platform"), str):
            errs.append("extra['kv_hierarchy'] has no 'platform' label")
        if not _is_num(kh.get("overcommit")) or kh.get("overcommit", 0) < 2:
            errs.append("kv_hierarchy.overcommit missing or < 2 — the "
                        "workload never forced real pool exhaustion")
        for mode in ("async", "sync"):
            row = kh.get(mode)
            if not isinstance(row, dict):
                errs.append(f"kv_hierarchy.{mode} missing or not a dict")
                continue
            for flag in ("tokens_identical", "all_completed",
                         "conserved_every_step", "host_pool_drained",
                         "no_stranded_spills"):
                if row.get(flag) is not True:
                    errs.append(f"kv_hierarchy.{mode}.{flag} must be True")
            for k in ("preemptions", "disk_demotions", "disk_promotions"):
                if not _is_num(row.get(k)) or row.get(k, 0) < 1:
                    errs.append(f"kv_hierarchy.{mode}.{k} missing or < 1 "
                                "— the three-tier ladder was never "
                                "exercised")
        arow = kh.get("async")
        if isinstance(arow, dict) and (
                not _is_num(arow.get("harvests"))
                or arow.get("harvests", 0) < 1):
            errs.append("kv_hierarchy.async.harvests missing or < 1 — "
                        "the async pipeline never deferred a readback")
        ab = kh.get("async_vs_sync")
        if not isinstance(ab, dict):
            errs.append("kv_hierarchy.async_vs_sync missing or not a dict")
        else:
            if ab.get("async_p99_reduced") is not True:
                errs.append("kv_hierarchy.async_vs_sync.async_p99_reduced "
                            "must be True — the deferred harvest did not "
                            "beat the blocking readback")
            for k in ("p99_preempt_swap_io_s_async",
                      "p99_preempt_swap_io_s_sync"):
                if not _is_num(ab.get(k)) or ab.get(k, -1) < 0:
                    errs.append(f"kv_hierarchy.async_vs_sync.{k} missing "
                                "or negative")
        qs = kh.get("quant_spill")
        if not isinstance(qs, dict):
            errs.append("kv_hierarchy.quant_spill missing or not a dict")
        else:
            if qs.get("tokens_identical") is not True:
                errs.append("kv_hierarchy.quant_spill.tokens_identical "
                            "must be True (vs the int8 never-evicted "
                            "reference)")
            ratio = qs.get("spill_bytes_ratio")
            if not _is_num(ratio) or ratio < 3.0:
                errs.append("kv_hierarchy.quant_spill.spill_bytes_ratio "
                            "missing or < 3 — the int8 shrink never "
                            "reached the swap path")
        if not _is_num(kh.get("measured_swap_gbps")):
            errs.append("kv_hierarchy.measured_swap_gbps missing or not "
                        "a number — no calibration round-trip was timed")

    # Windowed time-series + burn-rate alerts (ISSUE 19): CPU-runnable
    # forced-overload discrimination run, so always present; when
    # measured it must prove the in-bench assertions held (>= 1 overload
    # page whose iteration falls INSIDE the forced-overload burst, ZERO
    # alerts stamped in either calm phase, windowed-delta conservation
    # against the engine's own counters, and ts+alerts on/off token +
    # host-sync bit-parity) and keep the alert taxonomy CLOSED — a new
    # kind must be added to telemetry/alerts.py ALERT_KINDS, never
    # invented ad hoc in the bench output
    ta = e.get("ts_alerts")
    if not isinstance(ta, dict):
        errs.append("extra['ts_alerts'] missing or not a dict (the "
                    "forced-overload alert run is CPU-runnable — emit "
                    "error/skipped entries rather than dropping it)")
    elif "error" not in ta and "skipped_reason" not in ta:
        from deeplearning4j_tpu.telemetry.alerts import ALERT_KINDS
        if not isinstance(ta.get("platform"), str):
            errs.append("extra['ts_alerts'] has no 'platform' label")
        for flag in ("conservation", "tokens_identical", "sync_parity"):
            if ta.get(flag) is not True:
                errs.append(f"ts_alerts.{flag} must be True — the "
                            "in-bench invariant assertion did not hold")
        if not _is_num(ta.get("overload_alerts_in_burst")) \
                or ta.get("overload_alerts_in_burst", 0) < 1:
            errs.append("ts_alerts.overload_alerts_in_burst missing or "
                        "< 1 — the forced overload never paged")
        if ta.get("alerts_in_calm") != 0:
            errs.append("ts_alerts.alerts_in_calm must be 0 — the "
                        "monitor alerted on a calm phase (threshold "
                        "noise, not discrimination)")
        kinds = ta.get("alert_kinds")
        if not isinstance(kinds, dict) or set(kinds) != set(ALERT_KINDS):
            errs.append("ts_alerts.alert_kinds must be keyed by exactly "
                        "the closed alert taxonomy "
                        "(telemetry/alerts.py ALERT_KINDS)")
        elif any(not _is_num(v) or v < 0 for v in kinds.values()):
            errs.append("ts_alerts.alert_kinds values must be "
                        "non-negative counts")
        for k in ("peak_burn_rate_short", "slo_violations",
                  "ts_samples", "host_syncs", "short_window"):
            if not _is_num(ta.get(k)) or ta.get(k, -1) < 0:
                errs.append(f"ts_alerts.{k} missing or negative")

    # Decision journal record/replay (ISSUE 20): CPU-runnable round-trip
    # on the forced-overload schedule, so always present; when measured
    # it must prove the in-bench assertions held (bit-identical replayed
    # tokens, deterministic-alert-count parity, divergence localizer
    # None) and that journaling stayed an observability cost — under 1%
    # of the recorded run's wall (O(decisions) host dict appends, never
    # O(tokens) of device work)
    jr = e.get("journal_replay")
    if not isinstance(jr, dict):
        errs.append("extra['journal_replay'] missing or not a dict (the "
                    "record/replay round-trip is CPU-runnable — emit "
                    "error/skipped entries rather than dropping it)")
    elif "error" not in jr and "skipped_reason" not in jr:
        if not isinstance(jr.get("platform"), str):
            errs.append("extra['journal_replay'] has no 'platform' label")
        for flag in ("replay_token_parity", "alert_parity",
                     "divergence_free"):
            if jr.get(flag) is not True:
                errs.append(f"journal_replay.{flag} must be True — the "
                            "in-bench replay assertion did not hold")
        if not _is_num(jr.get("overhead_frac")) \
                or not 0 <= jr.get("overhead_frac", -1) < 0.01:
            errs.append("journal_replay.overhead_frac missing or >= 0.01 "
                        "— journaling must cost < 1% of recorded wall")
        for k in ("records", "journal_bytes", "host_syncs"):
            if not _is_num(jr.get(k)) or jr.get(k, 0) <= 0:
                errs.append(f"journal_replay.{k} missing or not positive "
                            "— the recorded run journaled nothing")

    # Latency blame ledger (ISSUE 14): CPU-runnable forced-contention
    # attribution run, so always present; when measured it must prove the
    # in-bench assertions held (per-request conservation, ledger-on/off
    # token + host-sync parity), have found real cross-request
    # interference, and keep the cause taxonomy CLOSED — a new cause key
    # must be added to telemetry/blame.py (and documented in PERF.md),
    # never invented ad hoc in the bench output
    ba = e.get("blame_attribution")
    if not isinstance(ba, dict):
        errs.append("extra['blame_attribution'] missing or not a dict "
                    "(the forced-contention blame run is CPU-runnable — "
                    "emit error/skipped entries rather than dropping it)")
    elif "error" not in ba and "skipped_reason" not in ba:
        from deeplearning4j_tpu.telemetry.blame import CAUSES
        if not isinstance(ba.get("platform"), str):
            errs.append("extra['blame_attribution'] has no 'platform' label")
        for flag in ("conserved", "tokens_identical", "sync_parity"):
            if ba.get(flag) is not True:
                errs.append(f"blame_attribution.{flag} must be True — the "
                            "in-bench invariant assertion did not hold")
        if not _is_num(ba.get("interference_edges")) \
                or ba.get("interference_edges", 0) < 1:
            errs.append("blame_attribution.interference_edges missing or "
                        "< 1 — forced contention found no cross-request "
                        "interference")
        totals = ba.get("cause_totals_s")
        if not isinstance(totals, dict) or set(totals) != set(CAUSES):
            errs.append("blame_attribution.cause_totals_s must be keyed by "
                        "exactly the closed cause taxonomy "
                        "(telemetry/blame.py CAUSES)")
        elif any(not _is_num(v) or v < 0 for v in totals.values()):
            errs.append("blame_attribution.cause_totals_s values must be "
                        "non-negative seconds")
        for side in ("violators", "attainers"):
            row = ba.get(side)
            if not isinstance(row, dict) or not _is_num(row.get("n")):
                errs.append(f"blame_attribution.{side} missing numeric 'n'")
                continue
            tops = row.get("top")
            if not isinstance(tops, list):
                errs.append(f"blame_attribution.{side}.top missing — the "
                            "docs render this table")
                continue
            for i, pair in enumerate(tops):
                if not (isinstance(pair, (list, tuple)) and len(pair) == 2
                        and pair[0] in CAUSES and _is_num(pair[1])
                        and pair[1] >= 0):
                    errs.append(f"blame_attribution.{side}.top[{i}] must be "
                                "a [cause-from-taxonomy, seconds>=0] pair")

    # Quantized KV A/B (ISSUE 15): CPU-runnable, so always present; when
    # measured it must prove the in-bench sync-parity assertion held and
    # carry the ACCURACY numbers next to the throughput ones — a quant
    # speedup reported without its divergence count is not a result. The
    # pool-byte ratio must show a real shrink (int8 payload + scale
    # overhead < half of any float pool it displaces), and divergence is
    # bounded: the disclosed gate is < 2% of greedy tokens.
    qk = e.get("quantized_kv")
    if not isinstance(qk, dict):
        errs.append("extra['quantized_kv'] missing or not a dict (the "
                    "quantized-KV A/B is CPU-runnable — emit error/skipped "
                    "entries rather than dropping it)")
    elif "error" not in qk and "skipped_reason" not in qk:
        if not isinstance(qk.get("platform"), str):
            errs.append("extra['quantized_kv'] has no 'platform' label")
        if qk.get("sync_parity") is not True:
            errs.append("quantized_kv.sync_parity must be True — the "
                        "quantize seam added a host sync")
        for k in ("tokens_per_sec_quant", "tokens_per_sec_float",
                  "kv_bytes_per_token_quant", "kv_bytes_per_token_float",
                  "max_abs_logprob_delta"):
            if not _is_num(qk.get(k)) or qk.get(k, -1) < 0:
                errs.append(f"quantized_kv.{k} missing or negative")
        ratio = qk.get("kv_pool_bytes_ratio")
        if not _is_num(ratio) or not (0 < ratio < 0.5):
            errs.append("quantized_kv.kv_pool_bytes_ratio must be in "
                        "(0, 0.5) — the int8 pool (payload + scales) is "
                        "a strict shrink vs any float dtype; >= 0.5 "
                        "means a dequantized copy or scale bloat")
        div, tot = qk.get("greedy_tokens_diverged"), \
            qk.get("greedy_tokens_total")
        if not _is_num(div) or not _is_num(tot) or tot <= 0:
            errs.append("quantized_kv divergence counters missing "
                        "(greedy_tokens_diverged / greedy_tokens_total)")
        elif div > 0.02 * tot:
            errs.append(f"quantized_kv greedy divergence {div}/{tot} "
                        "exceeds the disclosed 2% gate — quantization "
                        "is changing outputs, not just bytes")
        cap = qk.get("capacity_probe")
        if not isinstance(cap, dict) \
                or not _is_num(cap.get("resident_seqs_max_quant")) \
                or not _is_num(cap.get("resident_seqs_max_float")):
            errs.append("quantized_kv.capacity_probe missing resident-"
                        "sequence counts (the byte-equal capacity face "
                        "of the bytes/token reduction)")
        elif cap["resident_seqs_max_quant"] \
                < cap["resident_seqs_max_float"]:
            errs.append("quantized_kv.capacity_probe: quantized pool at "
                        "an equal byte budget holds FEWER sequences — "
                        "byte accounting or admission regressed")

    # prefix_radix (ISSUE 16): the radix-tree prefix cache A/B on a
    # seeded multi-turn/fork session mix. When measured it must prove
    # BOTH in-bench parity assertions held (greedy tokens AND the
    # host-sync count — the tree is host bookkeeping; a hidden readback
    # is a regression even at equal tokens), report a sane hit-token
    # fraction, and show fork branches actually shared pre-fork blocks —
    # a radix cache whose forks re-prefill is just the linear registry
    # with extra steps.
    pr = e.get("prefix_radix")
    if not isinstance(pr, dict):
        errs.append("extra['prefix_radix'] missing or not a dict (the "
                    "radix prefix-cache A/B is CPU-runnable — emit "
                    "error/skipped entries rather than dropping it)")
    elif "error" not in pr and "skipped_reason" not in pr:
        if not isinstance(pr.get("platform"), str):
            errs.append("extra['prefix_radix'] has no 'platform' label")
        if pr.get("token_parity") is not True:
            errs.append("prefix_radix.token_parity must be True — the "
                        "radix tree changed decoded tokens")
        if pr.get("sync_parity") is not True:
            errs.append("prefix_radix.sync_parity must be True — the "
                        "radix tree added a host sync")
        hit = pr.get("hit_token_frac")
        if not _is_num(hit) or not (0 <= hit <= 1):
            errs.append("prefix_radix.hit_token_frac must be a number "
                        "in [0, 1] (prefix hit tokens / prompt tokens)")
        saved = pr.get("flops_saved_frac")
        if not _is_num(saved) or not (0 <= saved <= 1):
            errs.append("prefix_radix.flops_saved_frac must be a number "
                        "in [0, 1] (follow-up prefill FLOPs saved)")
        fork = pr.get("fork_prefix_hit_tokens")
        if not _is_num(fork) or fork <= 0:
            errs.append("prefix_radix.fork_prefix_hit_tokens must be "
                        "> 0 — forked branches shared no pre-fork "
                        "blocks")

    # disaggregated prefill/decode A/B (ISSUE 17): CPU-runnable on forced
    # host devices, so always present; when measured the parity gate must
    # have held (a disagg run that drifts from colocated tokens is a
    # broken transfer seam, not a data point), both workload mixes must be
    # present with both sides' goodput and a declared winner, the
    # different-winners headline must be an explicit boolean (an honest
    # "False" beats a silently dropped mix), and the transfer block must
    # show KV bytes actually migrated
    da = e.get("serving_disagg_ab")
    if not isinstance(da, dict):
        errs.append("extra['serving_disagg_ab'] missing or not a dict "
                    "(the disagg A/B runs on forced host devices — emit "
                    "error/skipped entries rather than dropping it)")
    elif "error" not in da and "skipped_reason" not in da:
        if not isinstance(da.get("platform"), str):
            errs.append("extra['serving_disagg_ab'] has no 'platform' "
                        "label")
        if da.get("token_parity") is not True:
            errs.append("serving_disagg_ab.token_parity must be True — "
                        "the disagg group drifted from the colocated "
                        "greedy token stream")
        if not isinstance(da.get("different_winners"), bool):
            errs.append("serving_disagg_ab.different_winners must be an "
                        "explicit boolean (disclose the loss rather than "
                        "omitting the claim)")
        mixes = da.get("mixes")
        if not isinstance(mixes, dict):
            errs.append("serving_disagg_ab.mixes missing or not a dict")
        else:
            for mix in ("ttft_heavy", "tpot_heavy"):
                row = mixes.get(mix)
                if not isinstance(row, dict):
                    errs.append(f"serving_disagg_ab.mixes.{mix} missing "
                                "or not a dict (both mixes must run)")
                    continue
                if row.get("winner") not in ("colocated", "disagg",
                                             "tie"):
                    errs.append(f"serving_disagg_ab.mixes.{mix}.winner "
                                "must be 'colocated', 'disagg', or 'tie'")
                for side in ("colocated", "disagg"):
                    s = row.get(side)
                    if not isinstance(s, dict) or not all(
                            _is_num(s.get(k)) for k in
                            ("goodput", "ttft_p99_s")):
                        errs.append(f"serving_disagg_ab.mixes.{mix}."
                                    f"{side} must carry numeric goodput/"
                                    "ttft_p99_s")
        tr = da.get("transfer")
        if not isinstance(tr, dict) or not _is_num(tr.get("bytes")) \
                or tr.get("bytes", 0) <= 0:
            errs.append("serving_disagg_ab.transfer.bytes missing or "
                        "<= 0 — the disagg side never migrated any KV")

    # every measurement dict carries a platform label
    for name, entry in e.items():
        if not isinstance(entry, dict) or "error" in entry:
            continue
        if any(k in entry for k in _MEASUREMENT_KEYS):
            if "platform" not in entry:
                errs.append(f"extra['{name}'] is a measurement dict without "
                            "a 'platform' label")

    # roofline_table rows (auto-generated attribution)
    table = e.get("roofline_table")
    if table is not None:
        if not isinstance(table, list):
            errs.append("extra['roofline_table'] is not a list")
        else:
            for i, row in enumerate(table):
                if not isinstance(row, dict):
                    errs.append(f"roofline_table[{i}] is not a dict")
                    continue
                for k in _ROOFLINE_ROW_REQ:
                    if k not in row:
                        errs.append(f"roofline_table[{i}] missing '{k}'")
                if not isinstance(row.get("function"), str):
                    errs.append(f"roofline_table[{i}].function not a string")
                if not isinstance(row.get("platform"), str):
                    errs.append(f"roofline_table[{i}].platform not a string")
                m = row.get("measured_ms")
                if m is not None and (not _is_num(m) or m < 0):
                    errs.append(f"roofline_table[{i}].measured_ms invalid: "
                                f"{m!r}")
                mfu = row.get("mfu")
                if mfu is not None and not (_is_num(mfu) and 0 < mfu < 1):
                    errs.append(
                        f"roofline_table[{i}] ('{row.get('function')}') mfu "
                        f"{mfu!r} outside (0, 1) — implies past peak or a "
                        "degenerate measurement")
                xf = row.get("x_floor")
                if xf is not None and (not _is_num(xf) or xf <= 0):
                    errs.append(f"roofline_table[{i}].x_floor invalid: {xf!r}")
    return errs


def assert_valid(art: dict) -> None:
    """Raise AssertionError listing every violation (bench.py gate)."""
    errs = validate_artifact(art)
    assert not errs, "bench artifact schema violations:\n" + \
        "\n".join(f"  - {x}" for x in errs)
