"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
a ``breakdown``, and last ``checks``: each number compared with the
reference beside its limit. Standard error ends with the same checks.

Exits non-zero and prints no result unless every device JAX sees is a
TPU whose kind is in ``bench/peaks.json``, and there are as many as the
cell asks for. The persistent compilation cache lives in
``<checkout>/.jax_cache``, so only a cell's first run compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class NoDevice(RuntimeError):
    """The machine lacks the chips the cell asks for."""


def chips_for(cell, peaks: dict, devices) -> list:
    """The cell's devices, or NoDevice: all TPUs, of a kind with peaks."""
    if not devices or any(d.platform != "tpu" for d in devices):
        raise NoDevice(f"no TPU: JAX sees {devices}")
    kinds = {d.device_kind for d in devices}
    if not kinds <= set(peaks):
        raise NoDevice(f"device kind {sorted(kinds)} has no peaks in "
                       f"bench/peaks.json ({sorted(peaks)})")
    if len(devices) < cell.chips:
        raise NoDevice(f"the cell needs {cell.chips} chips, JAX sees "
                       f"{len(devices)}")
    return list(devices[:cell.chips])


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
    from bench import manifest
    cell = manifest.load_cell(args.workload)
    peaks = manifest.load_json(manifest.HERE / "peaks.json")
    import jax
    from bench.harness import run_cell, use_checkout_cache
    use_checkout_cache()
    try:
        devices = chips_for(cell, peaks, jax.devices())
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), T_START,
                   peaks[devices[0].device_kind], devices)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
