"""The reduction of the engine's own spans, stamps and counters
(``bench/engine_probe.py``): idle gaps put down to the innermost engine
span, the arithmetic of the four readings, and one tiny run on the CPU
whose stamps add up to the harness's time to first token."""
import types

import pytest

import bench_tiny
from bench import engine_probe as ep
from bench import stats


def test_gaps_inside_nested_engine_spans_go_to_the_innermost():
    """engine.admit [1, 5] holds engine.prefill [2, 3], both inside a
    bench.step [0.5, 8]; a gap outside any engine span keeps the rule of
    bench/trace.py (the bench span that overlaps it most)."""
    dev = {"/device:TPU:0": [("fusion.1", 0.0, 1.5), ("fusion.2", 2.5, 2.8),
                             ("fusion.3", 4.0, 6.0), ("fusion.4", 9.5, 10.0)],
           "/device:TPU:1": [("fusion.1", 0.0, 10.0)]}
    host = [("bench.window", 0.0, 10.0), ("bench.step", 0.5, 8.0),
            ("bench.wait", 8.0, 10.0), ("engine.admit", 1.0, 5.0),
            ("engine.prefill", 2.0, 3.0), ("engine.fetch", 6.5, 7.0)]
    idle = ep.idle_by_span(dev, host)
    # chip 0 idle: [1.5, 2.5] admit 0.5 + prefill 0.5; [2.8, 4] prefill
    # 0.2 + admit 1.0; [6, 9.5] fetch 0.5, and outside the engine spans
    # [6, 6.5] to step, [7, 9.5] to wait (1.5 of it against step's 1.0);
    # chip 1 never idle: each number is halved
    assert idle == pytest.approx({"engine.admit": 0.75,
                                  "engine.prefill": 0.35,
                                  "engine.fetch": 0.25,
                                  "bench.wait": 1.25,
                                  "bench.step": 0.25})
    assert sum(idle.values()) == pytest.approx((1.0 + 1.2 + 3.5) / 2)
    assert ep.idle_ms_per_quantum(idle, 5) == pytest.approx(
        1e3 * (0.75 + 0.35 + 0.25) / 5)
    assert ep.idle_ms_per_quantum(idle, 0) is None


def test_innermost_segments():
    spans = [("a", 0.0, 10.0), ("b", 2.0, 4.0), ("c", 3.0, 5.0),
             ("d", 12.0, 13.0)]
    assert ep.innermost(spans) == [(0.0, 2.0, "a"), (2.0, 3.0, "b"),
                                   (3.0, 5.0, "c"), (5.0, 10.0, "a"),
                                   (12.0, 13.0, "d")]
    assert ep.attribute_innermost([(11.0, 12.5)], spans, []) == \
        pytest.approx({"d": 0.5, "host:none": 1.0})


def test_prefill_ms_per_ktok():
    assert ep.prefill_ms_per_ktok((1.0, 100), (1.5, 2100)) == \
        pytest.approx(250.0)
    assert ep.prefill_ms_per_ktok((1.0, 100), (1.0, 100)) is None


def test_readings_arithmetic():
    """Queue wait counts a request never admitted until the drain ended;
    the hold runs from t_first to the return of the step that served it;
    the prefill counters are read at the window's open and loop end."""
    t_ref = 100.0
    R = lambda rid, sub, adm, first: types.SimpleNamespace(  # noqa: E731
        rid=rid, t_submit=sub, t_admit=adm, t_first=first)
    S = lambda due, first: types.SimpleNamespace(  # noqa: E731
        due=due, t_first=first)
    reqs = [R(0, 100.0, 100.1, 100.3), R(1, 101.0, 101.5, 101.6),
            R(2, 102.0, None, None)]
    sts = [S(0.0, 0.5), S(1.0, 1.8), S(2.0, None)]
    watch = types.SimpleNamespace(
        served={0: 100.5, 1: 101.8}, sent={0: 100.0, 1: 101.0},
        snaps=[(99.0, (2.0, 1000)), (100.5, (2.4, 3000)),
               (110.0, (3.0, 4000)), (130.0, (4.0, 5000))])
    watch.at = ep.Watch.at.__get__(watch)
    out = ep.readings(watch, reqs, sts, {"drain_end": 20.0,
                                         "loop_end": 10.5})
    assert out["admission.queue_wait_p90_ms"] == pytest.approx(
        1e3 * stats.percentile([0.1, 0.5, t_ref + 20.0 - 102.0], 90))
    assert out["admission.first_token_hold_p90_ms"] == pytest.approx(
        1e3 * stats.percentile([0.2, 0.2], 90))
    assert out["admission.prefill_ms_per_ktok"] == pytest.approx(
        1e6 * 1.0 / 3000)
    assert out["ttft_identity_ms"] == pytest.approx(0.0, abs=1e-9)
    assert out["sent"] == 3 and out["first_tokens"] == 2


def test_tiny_run_stamps_add_up_to_the_harness_ttft(monkeypatch):
    """On one tiny run, queue wait + (t_first - t_admit) + hold is the
    harness's time to first token less the generator's lateness, within
    1 ms: the program's stamps and the harness share one clock."""
    import jax
    from bench.harness import CompileClock, measure, prepare
    from bench.serving import engines
    bench_tiny.smoke_registry(monkeypatch)
    cell = bench_tiny.tiny_cell()
    server, arrivals = prepare(cell, 3, 2.0, jax.devices())
    watch = ep.Watch(server, engines(server))
    reqs, sts, steps, facts = measure(watch, arrivals, 2.0, False,
                                      CompileClock())
    out = ep.readings(watch, reqs, sts, facts)
    assert out["first_tokens"] == out["sent"] == len(reqs) > 3
    assert out["ttft_identity_ms"] < 1.0, out
    for r in reqs:
        assert r.t_submit <= r.t_admit <= r.t_first <= watch.served[r.rid]
    assert out["admission.queue_wait_p90_ms"] >= 0
    assert out["admission.first_token_hold_p90_ms"] >= 0
    assert out["admission.prefill_ms_per_ktok"] > 0
