"""The check that decides ``correct``: a sound run passes it and its
control (the reference computed in float8) fails it. Tiny cells on the
CPU; the chip-size readings are in PERF.md."""
import pytest

from bench_tiny import run_tiny, smoke_registry, tiny_cell

SEEDS = (1, 2, 3)


@pytest.mark.parametrize("config", ["tiny-nemo", "tiny-danube"])
def test_sound_run_is_correct(monkeypatch, config):
    out = run_tiny(monkeypatch, config, seed=11)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] == 12
    assert list(out["checks"]) == ["max_logit_gap", "requests_failed"]
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms",
                                   "tpot_p50_ms", "out_tok_s_per_chip",
                                   "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())


@pytest.mark.parametrize("config", ["tiny-nemo", "tiny-danube"])
def test_control_fails_where_the_program_passes(monkeypatch, config):
    """The reference computed in float8, at the program's own served
    tokens, reads a gap above the limit on every seed; the program reads
    one below it on the same requests."""
    import jax
    from bench import harness
    smoke_registry(monkeypatch)
    cell = tiny_cell(config)
    limit = cell.config["limits"]["max_logit_gap"]
    clock = harness.CompileClock()
    for seed in SEEDS:
        server, arrivals = harness.prepare(cell, seed, 3.0, jax.devices())
        reqs, _, _, facts = harness.measure(server, arrivals, 3.0, False,
                                            clock)
        gaps = harness.check(cell.config, seed, reqs, controls=("fp8",))
        assert facts["failed"] == 0
        assert gaps["program"] <= limit < gaps["controls"]["fp8"], gaps
