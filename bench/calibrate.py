"""Readings that the ``correct`` limits are set from: the program's
widest logit gap and the controls' (the reference in a lower precision),
seed after seed, in one process.

    python3 bench/calibrate.py --workload nemo-l8.chat --seconds 10 \\
        --seeds 101 102 103 --controls fp8 int8

Each seed builds the server over that seed's weights, serves a short
window of the cell's own traffic (same rate, same lengths' shape, the
longest requests included) and drains it, frees the server, and runs the
reference over the same sample of requests a benchmark run checks. One
JSON line per seed. The benchmark's own runs never run the controls.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", nargs="*", default=["fp8", "int8"])
    args = ap.parse_args(argv)
    sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
    import jax
    from bench import harness, manifest
    harness.use_checkout_cache()
    cell = manifest.load_cell(args.workload)
    clock = harness.CompileClock()
    devices = jax.devices()[:cell.chips]
    for seed in args.seeds:
        server, arrivals = harness.prepare(cell, seed, args.seconds, devices)
        reqs, _, _, facts = harness.measure(server, arrivals, args.seconds,
                                            False, clock)
        del server
        gc.collect()
        gaps = harness.check(cell.config, seed, reqs,
                             controls=args.controls)
        print(json.dumps({"seed": seed, "failed": facts["failed"],
                          "sent": facts["sent"],
                          "drain_s": facts["drain_s"], **(gaps or {})}),
              flush=True)


if __name__ == "__main__":
    main()
