"""Find a cell's knee: serve its traffic at several fixed rates, one
window each, in one process, and print the end-to-end numbers of each.

    python3 bench/sweep.py --workload nemo-l8.chat --seconds 30 \\
        --rates 1.5 2.5 3.5 4.5 --seed 7

The knee is the highest rate the server sustains: its time to first
token does not grow through the window (``ttft_growth``, the median of
the last third of arrivals over the first third, stays near 1) and no
request is still waiting for its first token when the window closes
(``unstarted_at_close``). A sweep does not drain: when the window closes
the engines drop what they hold, and the next rate starts empty. The
cells run at about four fifths of the knee; PERF.md records each sweep.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
    import jax
    from bench import harness, manifest
    from bench.serving import engines
    from bench.stats import percentile
    harness.use_checkout_cache()
    cell = manifest.load_cell(args.workload)
    vocab = manifest.model(cell.config).model_shape(cell.config)[
        "vocab_size"]
    clock = harness.CompileClock()
    devices = jax.devices()[:cell.chips]
    gen = manifest.generator(cell.traffic["kind"])
    server = None
    for rate in args.rates:
        mix = dict(cell.traffic, rate_rps=rate)
        if server is None:
            server, arrivals = harness.prepare(cell, args.seed, args.seconds,
                                               devices, mix)
        else:
            arrivals = gen.generate(mix, args.seconds, args.seed, vocab)
            plens = [len(a.prompt) for a in arrivals]
            harness.warm_up(engines(server), harness.warm_requests(
                engines(server)[0], plens,
                max(len(a.prompt) + a.max_new for a in arrivals)), vocab)
        reqs, stats, steps, facts = harness.measure(
            server, arrivals, args.seconds, False, clock, drain_limit=0.0)
        for eng in engines(server):
            eng.take_pending()
            eng.abort()
        getattr(server, "queue", []).clear()
        rec = harness.record(cell, server, args.seconds, {}, stats, steps,
                             facts)
        row = {"rate_rps": rate, "sent": facts["sent"],
               "unstarted_at_close": facts["unstarted_at_close"],
               "compiles_in_window": facts["compiles_in_window"],
               "out_tok_s_per_chip": manifest.reader("out_tok_s_per_chip")(
                   rec)}
        # waits not over at the close count until the close
        wait = [(r.t_first if r.t_first is not None else facts["loop_end"])
                - r.due for r in stats]
        row["ttft_p90_ms_lower_bound"] = 1e3 * percentile(wait, 90)
        third = args.seconds / 3
        early = [w for w, r in zip(wait, stats) if r.due < third]
        late = [w for w, r in zip(wait, stats) if r.due >= 2 * third]
        row["ttft_growth"] = statistics.median(late) / statistics.median(
            early)
        row["tpot_p50_ms"] = 1e3 * percentile(
            [(r.t_last - r.t_first) / (r.n_out - 1) for r in stats
             if r.n_out > 1], 50)
        row["decode.step_ms"] = manifest.reader("decode.step_ms")(rec)
        row["engine.prefill_share"] = manifest.reader(
            "engine.prefill_share")(rec)
        row["router.tier_load_ratio"] = manifest.reader(
            "router.tier_load_ratio")(rec)
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
