"""Metric arithmetic on hand-made records: percentiles over all requests,
the window's token rate, the roofline and MFU counts against values
worked by hand, and the spread of decoding over a router's tiers."""
import numpy as np
import pytest

import bench_tiny  # noqa: F401
from bench import manifest, stats
from bench.harness import Record, ReqStat, StepStat, _keys

SHAPE = {"hidden_size": 8, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 2, "intermediate_size": 16,
         "vocab_size": 32, "num_hidden_layers": 3}
PEAKS = {"bf16_flops_per_s": 1000.0, "hbm_bytes_per_s": 100.0}
PLAIN = manifest.model({})       # a configuration that names no module


def _rec(requests, steps, window=10.0, trace=None, loop_end=10.5):
    return Record(cell="c", chips=2, config={}, shape=SHAPE,
                  decode_quantum=4, window_s=window, peaks=PEAKS,
                  requests=requests, steps=steps, trace=trace,
                  loop_end=loop_end)


def test_percentile_matches_numpy():
    rng = np.random.default_rng(0)
    for n in (1, 2, 7, 100):
        x = rng.random(n)
        for q in (50, 90, 95):
            assert stats.percentile(x, q) == pytest.approx(
                np.percentile(x, q))


def test_spread_is_iqr_over_median():
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(
        (5.25 - 1.75) / 3.5)


def test_latency_metrics_over_all_requests():
    reqs = [ReqStat(10, 5, due=d, t_first=d + f, t_last=d + f + 4 * g,
                    n_out=5)
            for d, f, g in [(0.0, 0.1, 0.01), (1.0, 0.3, 0.02),
                            (2.0, 0.2, 0.03), (3.0, 2.0, 0.05)]]
    rec = _rec(reqs, [])
    read = lambda n: manifest.reader(n)(rec)  # noqa: E731
    assert read("ttft_p90_ms") == pytest.approx(
        1e3 * np.percentile([0.1, 0.3, 0.2, 2.0], 90))
    assert read("tpot_p50_ms") == pytest.approx(25.0)
    assert read("tpot_p90_ms") == pytest.approx(
        1e3 * np.percentile([0.01, 0.02, 0.03, 0.05], 90))


def test_window_rate_counts_steps_that_returned_inside():
    steps = [StepStat(0.0, 4.0, 3.0, 1, emitted=30),
             StepStat(4.0, 10.0, 5.0, 1, emitted=50),
             StepStat(10.0, 10.4, 0.3, 1, emitted=7)]    # after the close
    rec = _rec([], steps)
    assert manifest.reader("out_tok_s_per_chip")(rec) == pytest.approx(
        80 / 10.0 / 2)
    assert manifest.reader("decode.step_ms")(rec) == pytest.approx(
        1e3 * 8.0 / (2 * 4))
    assert manifest.reader("engine.prefill_share")(rec) == pytest.approx(
        100 * (10.0 - 8.0) / 10.0)


def test_keys_of_consecutive_tokens():
    # tokens attending 5, 6, 7 positions
    assert _keys(5, 3) == 18 and _keys(1, 4) == 10 and _keys(9, 0) == 0


def test_counts_by_hand():
    # per block: 2*8*4*2 (q, o) + 2*8*2*2 (k, v) + 3*8*16 = 128+64+384
    assert PLAIN.layer_params(SHAPE) == 576
    assert PLAIN.attention_flops(SHAPE, 10) == 4 * 10 * 4 * 2 * 3
    # 10 keys: 10*2*2*2*2 B of K and V; 2 tokens: 2*2*4*2*2 B of q and o
    assert PLAIN.decode_attention_bytes(SHAPE, 10, 2) == 3 * (160 + 64)
    assert PLAIN.model_flops(SHAPE, 2, 10, 1) == (
        2 * 576 * 3 * 2 + 4 * 10 * 4 * 2 * 3 + 2 * 8 * 32)


def test_roofline_and_mfu_by_hand():
    class T:
        op_seconds = {"fusion.3": 1.0, "paged_flash_decode_gqa.1": 2.0}
    steps = [StepStat(0.0, 2.0, 1.5, 1, emitted=3, decode_tokens=2,
                      decode_keys=10, prefill_tokens=4, prefill_keys=10)]
    rec = _rec([], steps, trace=T())
    least = max(PLAIN.decode_attention_bytes(SHAPE, 10, 2) / 100.0,
                PLAIN.attention_flops(SHAPE, 10) / 1000.0)
    assert manifest.reader("paged_attn_roofline")(rec) == pytest.approx(
        100 * least / 2.0)
    flops = PLAIN.model_flops(SHAPE, 6, 20, 3)
    assert manifest.reader("step_mfu")(rec) == pytest.approx(
        100 * flops / (2.0 * 2 * 1000.0))


def test_roofline_without_its_kernel_is_an_error():
    class T:
        op_seconds = {"fusion.3": 1.0, "paged_flash_decode_mla.1": 2.0}
    decoded = [StepStat(0.0, 2.0, 1.5, 1, decode_tokens=2, decode_keys=10)]
    with pytest.raises(LookupError):
        manifest.reader("paged_attn_roofline")(_rec([], decoded, trace=T()))
    prefill_only = [StepStat(0.0, 2.0, 1.5, 1, prefill_tokens=4)]
    assert manifest.reader("paged_attn_roofline")(
        _rec([], prefill_only, trace=T())) is None


def test_ttft_counts_an_unserved_request_until_the_drain_ended():
    reqs = [ReqStat(10, 5, due=0.0, t_first=0.5, t_last=1.0, n_out=5),
            ReqStat(10, 5, due=2.0)]
    rec = _rec(reqs, [])
    rec.drain_end = 12.0
    assert manifest.reader("ttft_p90_ms")(rec) == pytest.approx(
        1e3 * np.percentile([0.5, 10.0], 90))


def test_trace_metrics_need_a_trace():
    rec = _rec([], [StepStat(0.0, 1.0, 1.0, 1, decode_tokens=1,
                             decode_keys=1)])
    assert manifest.reader("paged_attn_roofline")(rec) is None
    assert manifest.reader("device.idle_share")(rec) is None


def _tiers(decoded: list[dict], tiers=("t0", "t1", "t2", "t3")):
    steps = [StepStat(float(i), float(i) + 0.5, 0.4, len(d), per_tier=d)
             for i, d in enumerate(decoded)]
    rec = _rec([], steps)
    rec.tiers = tiers
    return manifest.reader("router.tier_load_ratio")(rec)


def test_tier_load_ratio_of_even_tiers_is_one():
    assert _tiers([{"t0": 8, "t1": 8, "t2": 8, "t3": 8},
                   {"t0": 4, "t1": 4, "t2": 4, "t3": 4}]) == pytest.approx(1)


def test_tier_load_ratio_counts_a_tier_that_never_stepped():
    # one tier of four idle: max 8 over mean 6
    assert _tiers([{"t0": 8, "t1": 8, "t2": 8},
                   {"t0": 8, "t1": 8, "t2": 8}]) == pytest.approx(4 / 3)
    # steps after the window's close do not count
    late = StepStat(10.0, 10.4, 0.3, 1, per_tier={"t3": 99})
    rec = _rec([], [StepStat(0.0, 1.0, 0.5, 2, per_tier={"t0": 6, "t1": 2}),
                    late])
    rec.tiers = ("t0", "t1")
    assert manifest.reader("router.tier_load_ratio")(rec) == pytest.approx(
        1.5)


def test_tier_load_ratio_of_one_engine_is_none():
    assert _tiers([{"": 8}, {"": 4}], tiers=()) is None
    assert _tiers([{}], tiers=("t0", "t1")) is None
