"""The trace reduction's arithmetic on hand-made intervals."""
from harness import trace


def test_union_clip_total_and_gaps():
    busy = trace.union([(0, 10), (5, 20), (30, 40), (40, 45)])
    assert busy == [(0, 20), (30, 45)]
    assert trace.total(trace.clip(busy, 10, 35)) == 15
    assert trace.gaps_of(busy, 0, 50_000) == [(45, 50_000)]
    assert trace.gaps_of([(0, 10_000), (20_000, 30_000)], 0, 30_000) == [(10_000, 20_000)]


def test_gap_goes_to_the_innermost_host_span_or_to_the_program():
    spans = [("bench.fit_call", 0, 1000), ("bench.readback", 800, 100),
             ("bench.listener", 2000, 500)]
    assert trace.attribute((820, 880), spans) == "bench.readback"
    assert trace.attribute((100, 200), spans) == "inside_program"
    assert trace.attribute((2100, 2200), spans) == "bench.listener"
    assert trace.attribute((5000, 6000), spans) == "inside_program"


def test_reduce_counts_whole_periods_between_marks():
    ns = 1_000_000
    ops = [("fusion.1", i * 100 * ns, 60 * ns) for i in range(5)] \
        + [("custom-call.2", i * 100 * ns + 60 * ns, 20 * ns) for i in range(5)]
    spans = [("bench.fit_call", i * 100 * ns, 90 * ns) for i in range(5)]
    r = trace.reduce({"/device:TPU:0": ops}, spans, "bench.fit_call")
    assert r.periods == 4 and abs(r.stretch_s - 0.4) < 1e-12
    assert abs(r.busy_s - 0.32) < 1e-12 and abs(r.idle_share - 0.2) < 1e-12
    assert abs(r.ops_s["fusion.1"] - 0.24) < 1e-12
    assert abs(r.ops_s["custom-call.2"] - 0.08) < 1e-12
    assert r.top_gaps()[0][0] == "inside_program"
    assert trace.reduce({"/device:TPU:0": ops}, spans[:1], "bench.fit_call") is None
    assert trace.reduce({}, spans, "bench.fit_call") is None


def test_exposed_collective_time():
    ev = [("all-reduce.1", 0, 100), ("fusion.2", 50, 100), ("all-reduce.3", 200, 50)]
    alone = trace.exposed(ev, lambda n: n.startswith("all-reduce"),
                          lambda n: n.startswith("fusion"), 0, 300)
    assert abs(alone - 100e-9) < 1e-15


def test_an_events_name_is_the_hlo_text_and_is_cut_to_a_label():
    call = ('%jvp__.22 = (bf16[100,8192,256]{2,1,0:T(8,128)(2,1)}, bf16[100,8192,256]'
            '{2,1,0}) custom-call(bf16[1,2]{0} %x), custom_call_target="tpu_custom_call"')
    assert trace.parse_op(call) == ("jvp__.22", "custom-call")
    assert trace.op_label(call) == "jvp__.22 custom-call tpu_custom_call"
    loop = "%while.7 = (s32[]{:T(128)}, f32[256,1024]{1,0:T(8,128)}) while((s32[]) %t), body=%b"
    assert trace.parse_op(loop)[1] in trace.CONTAINERS
    fusion = "%fusion.2445 = (bf16[64]{0:T(256)(128)(2,1)S(1)}, bf16[512,64,112,112]{0,1,3,2}) fusion(f32[64]{0} %a)"
    assert trace.op_label(fusion) == "fusion.2445"
    pool = "%select-and-scatter.17 = bf16[512,64,112,112]{0,1,3,2:T(8,128)(2,1)} select-and-scatter(bf16[512] %a)"
    assert trace.op_label(pool) == "select-and-scatter.17"
    assert trace.op_label("plain") == "plain"
