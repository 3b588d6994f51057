"""Operation and byte counts against hand-computed values, and the trace
reducer on a small recorded trace."""
import os

import pytest

from bench import counts, harness, trace_reduce

G = harness.config("granite3-2b.sd4")
D = harness.config("dscoder33b-l8.sd1")
DATA = os.path.join(os.path.dirname(__file__), "data")


def test_matmul_params_by_hand():
    # Granite 3.0 2B: q,o 2048x2048, k,v 2048x512, MLP 3x2048x8192 per
    # layer; 40 layers; tied head 2048x49155 counted once
    layer = 2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192
    assert layer == 60_817_408
    assert counts.matmul_params(G) == 40 * layer + 2048 * 49155
    # DeepSeek-Coder 33B stage: q,o 7168x7168, k,v 7168x1024, MLP
    # 3x7168x19200 -> 529.5 M per layer
    layer = 2 * 7168 * 7168 + 2 * 7168 * 1024 + 3 * 7168 * 19200
    assert layer == 530_317_312
    assert counts.matmul_params(D) == 8 * layer + 7168 * 32256


def test_kv_bytes_per_token_by_hand():
    assert counts.kv_bytes_per_token(G) == 80 * 1024          # 80 KiB
    assert counts.kv_bytes_per_token(G, layers=4) == 8 * 1024
    assert counts.kv_bytes_per_token(D) == 32 * 1024          # 32 KiB
    assert counts.kv_bytes_per_token(D, layers=1) == 4 * 1024


def test_attention_and_paged_least_by_hand():
    # one query over 1000 keys, 32 heads of 64, one layer: 4*1000*2048
    assert counts.attention_flops(G, 1, 1000, layers=1) == 8_192_000
    f, b = counts.paged_attention_least(G, 5, 1000, layers=2)
    assert f == 4 * 5 * 1000 * 32 * 64 * 2
    assert b == 2 * 2 * 8 * 64 * 2 * 1000 + 2 * 5 * 32 * 64 * 2 * 2
    assert counts.token_flops(G, 0) == 2 * counts.matmul_params(G) \
        + counts.attention_flops(G, 1, 1)


def _trace():
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, "small_trace.pbtxt")) as f:
        return trace_reduce.load(ProfileData.from_text_proto(f.read()))


def test_reducer_busy_idle_and_kernels():
    devs = _trace()
    assert [d.name for d in devs] == ["/device:TPU:0"]
    d = devs[0]
    # ops (ns): dot [1000, 4000), paged_attention [3000, 5000) overlaps,
    # paged_attention [7000, 8000), a slice of its result [8000, 8500), a
    # while [11000, 14000) holding fusion [12000, 13000);
    # union = 4000 + 1500 + 3000 = 8500
    assert trace_reduce.busy_ns(d) == pytest.approx(8500)
    assert trace_reduce.span_ns(d) == (1000, 14000)
    pa = trace_reduce.kernel_ops(d, "paged_attention")
    assert [(s, e) for _, s, e in pa] == [(3000, 5000), (7000, 8000)]
    assert trace_reduce.result_shape(pa[0][0]) == (32, 8, 24, 64)
    assert trace_reduce.short_name(pa[0][0]) == \
        "paged_attention.1 bf16[32,8,24,64] custom-call"
    # the while op [9000, 14000) holds fusion.3: only the leaf counts
    assert [n.split(" ")[0] for n, _, _ in trace_reduce.leaves(d)] == \
        ["%dot.1", "%paged_attention.1", "%paged_attention.1", "%slice.4",
         "%fusion.3"]
    gaps = trace_reduce.idle_gaps(d)
    assert gaps[0][0] == "before while.2 (s32[]) while"
    assert gaps[0][1] == pytest.approx(2.5e-6)
    s = trace_reduce.summarize(devs, window_s=20e-6)
    assert s["busy_s"] == pytest.approx(8.5e-6)
    assert s["window_s"] == pytest.approx(20e-6)
    assert dict(s["device_ops"]) == pytest.approx(
        {"dot.1 f32[8,8] dot": 3e-6,
         "paged_attention.1 bf16[32,8,24,64] custom-call": 3e-6,
         "fusion.3 f32[8] fusion": 1e-6,
         "slice.4 bf16[32,8,4,64] slice": 0.5e-6})
