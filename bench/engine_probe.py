"""Where a cell's time to first token and device idle time go, read from
the serving engine's own spans, request stamps and counters.

    python3 bench/engine_probe.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of the cell as ``bench/run.py`` makes it (same set-up, window and
drain; no reference check), with the server watched from outside: the
return of every ``step()`` and the engines' prefill counters after it.
Prints, as the last line of standard output, one JSON object:

- ``end_to_end``: the cell's end-to-end metrics, and ``decode.step_ms``;
- ``admission.queue_wait_p90_ms``: p90 over the requests sent in the
  window of ``Request.t_admit - t_submit``; a request never admitted
  waits until the drain ended;
- ``admission.first_token_hold_p90_ms``: p90 of the return of the
  ``step()`` that served the first token less ``Request.t_first``;
- ``admission.prefill_ms_per_ktok``: the engines' ``prefill_s`` over
  their ``prefill_tokens`` between the window's open and its loop's end,
  in ms per 1000 prompt tokens;
- ``ttft_identity_ms``: the widest gap between queue wait + (t_first -
  t_admit) + hold and the harness's time to first token less the
  generator's lateness; near 0 when the program's stamps and the
  harness's times share one clock;
- with ``--trace 1``: ``device.idle_share`` as ``bench/trace.py`` gives
  it, ``idle_s`` by span (each idle gap, or part of it, inside an
  ``engine.*`` span goes to the innermost one; the rest to the ``bench.*``
  span that overlaps it most, as ``bench/trace.py`` does), and
  ``engine.idle_ms_per_quantum``: the idle seconds inside ``engine.*``
  spans over the decode quanta of the traced window, in ms.

The benchmark's own result line carries none of these readings:
``bench/harness.py`` keeps neither the requests' stamps nor the engines'
counters in its record, and ``bench/trace.py`` keeps only ``bench.*``
host spans.
"""
from __future__ import annotations

import glob
import json
import shutil
import sys
import time
from collections import defaultdict
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]
ENGINE_PREFIX = "engine."


def innermost(spans) -> list[tuple[float, float, str]]:
    """Non-overlapping (start, end, name) segments covering the union of
    ``spans`` [(name, start, end)], each named by the innermost span over
    it: the one that started last (of two that started together, the one
    that ends first)."""
    cuts = sorted({t for _, s, e in spans for t in (s, e)})
    by_start = sorted(spans, key=lambda x: x[1])
    out, active, k = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(by_start) and by_start[k][1] <= a:
            active.append(by_start[k])
            k += 1
        active = [x for x in active if x[2] > a]
        if active:
            name = max(active, key=lambda x: (x[1], -x[2]))[0]
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1] = (out[-1][0], b, name)
            else:
                out.append((a, b, name))
    return out


def attribute_innermost(idle, engine_spans, other_spans) -> dict:
    """Idle seconds by span name: each part of a gap that lies inside an
    engine span goes to the innermost such span; the parts outside any go
    to the other span that overlaps each part most, as
    ``bench.trace.attribute`` does."""
    from bench.trace import attribute
    segs = innermost(engine_spans)
    out: dict = defaultdict(float)
    rest, j = [], 0
    for gs, ge in sorted(idle):
        while j < len(segs) and segs[j][1] <= gs:
            j += 1
        t, i = gs, j
        while i < len(segs) and segs[i][0] < ge:
            s, e, name = segs[i]
            if s > t:
                rest.append((t, s))
            lo, hi = max(s, t), min(e, ge)
            out[name] += hi - lo
            t = hi
            i += 1
        if t < ge:
            rest.append((t, ge))
    for k, v in attribute(rest, other_spans).items():
        out[k] += v
    return dict(out)


def load(path: str):
    """Device op events and the ``bench.*`` and ``engine.*`` host spans of
    one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    from bench.trace import DEVICE_PREFIX, HOST_PREFIX, OPS_LINE, op_name
    data = ProfileData.from_file(path)
    device_events, host_spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = [(op_name(e.name), e.start_ns * 1e-9,
                    (e.start_ns + e.duration_ns) * 1e-9)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if evs:
                device_events[plane.name] = evs
        elif plane.name.startswith("/host:"):
            host_spans.extend(
                (e.name, e.start_ns * 1e-9,
                 (e.start_ns + e.duration_ns) * 1e-9)
                for line in plane.lines for e in line.events
                if e.name.startswith((HOST_PREFIX, ENGINE_PREFIX)))
    return device_events, host_spans


def idle_by_span(device_events, host_spans) -> dict:
    """Idle seconds of the traced window by span, mean over the devices,
    from the same busy intervals as ``bench.trace.reduce_events``."""
    from bench.trace import WINDOW, gaps
    lo, hi = next((s, e) for n, s, e in host_spans if n == WINDOW)
    eng = [x for x in host_spans if x[0].startswith(ENGINE_PREFIX)]
    other = [x for x in host_spans
             if x[0] != WINDOW and not x[0].startswith(ENGINE_PREFIX)]
    total: dict = defaultdict(float)
    for evs in device_events.values():
        iv = [(s, e) for _, s, e in evs if e > lo and s < hi]
        for k, v in attribute_innermost(gaps(iv, lo, hi), eng,
                                        other).items():
            total[k] += v
    n = max(1, len(device_events))
    return {k: v / n for k, v in sorted(total.items(), key=lambda kv: -kv[1])}


def idle_ms_per_quantum(idle: dict, quanta: int) -> float | None:
    """Idle milliseconds inside ``engine.*`` spans per decode quantum."""
    if not quanta:
        return None
    return 1e3 * sum(v for k, v in idle.items()
                     if k.startswith(ENGINE_PREFIX)) / quanta


def prefill_ms_per_ktok(at_open, at_close) -> float | None:
    """(prefill_s, prefill_tokens) at the window's open and close -> ms
    of the ``engine.prefill`` spans per 1000 prompt tokens."""
    ds, dn = at_close[0] - at_open[0], at_close[1] - at_open[1]
    return 1e6 * ds / dn if dn else None


class Watch:
    """The server as the harness drives it, with the time of each
    ``submit()``, the return time of each ``step()`` and the engines'
    (prefill_s, prefill_tokens) after it, and for each request the return
    of the step that served its first token."""

    def __init__(self, server, engs):
        self.server, self.engs = server, engs
        self.live: list = []
        self.sent: dict = {}
        self.served: dict = {}
        self.snaps = [(time.perf_counter(), self.counters())]

    def counters(self) -> tuple[float, int]:
        return (sum(e.prefill_s for e in self.engs),
                sum(e.prefill_tokens for e in self.engs))

    def submit(self, req) -> None:
        self.sent[req.rid] = time.perf_counter()
        self.server.submit(req)
        self.live.append(req)

    def step(self):
        rep = self.server.step()
        t = time.perf_counter()
        for r in self.live:
            if r.out:
                self.served[r.rid] = t
        self.live = [r for r in self.live if not r.out]
        self.snaps.append((t, self.counters()))
        return rep

    def at(self, t: float) -> tuple[float, int]:
        """The counters after the last step that returned by ``t``."""
        return [c for s, c in self.snaps if s <= t][-1]


def readings(watch: Watch, reqs, stats, facts) -> dict:
    """The admission readings of one run (see the module's docstring)."""
    from bench.stats import percentile
    served = [(r, st, watch.served[r.rid]) for r, st in zip(reqs, stats)
              if r.rid in watch.served and st.t_first is not None]
    # the harness times steps from its own origin t_ref, stamping a
    # request's first token just after the watched step returned
    offsets = [t - st.t_first for _, st, t in served]
    t_ref = max(offsets)
    drain_end = t_ref + facts["drain_end"]
    sent = [r for r in reqs if r.t_submit is not None]
    waits = [(drain_end if r.t_admit is None else r.t_admit) - r.t_submit
             for r in sent]
    holds = [t - r.t_first for r, _, t in served]
    # the harness's time to first token less the generator's lateness,
    # both on the harness's clock, against the program's three parts
    identity = [abs((r.t_admit - r.t_submit) + (r.t_first - r.t_admit)
                    + (t - r.t_first)
                    - ((st.t_first - st.due)
                       - (watch.sent[r.rid] - t_ref - st.due)))
                for r, st, t in served]
    return {
        "admission.queue_wait_p90_ms": 1e3 * percentile(waits, 90),
        "admission.first_token_hold_p90_ms": 1e3 * percentile(holds, 90),
        "admission.prefill_ms_per_ktok": prefill_ms_per_ktok(
            watch.snaps[0][1], watch.at(t_ref + facts["loop_end"])),
        "ttft_identity_ms": 1e3 * max(identity),
        "sent": len(sent), "first_tokens": len(served)}


def probe(cell, seed: int, seconds: float, trace: bool, peaks: dict,
          devices) -> dict:
    """One watched run of ``cell``; returns the readings."""
    from bench import manifest
    from bench.harness import (TRACE_DIR, CompileClock, measure, prepare,
                               record)
    from bench.serving import engines
    from bench.trace import reduce_events
    clock = CompileClock()
    server, arrivals = prepare(cell, seed, seconds, devices)
    engs = engines(server)
    watch = Watch(server, engs)
    reqs, stats, steps, facts = measure(watch, arrivals, seconds, trace,
                                        clock)
    rec = record(cell, server, seconds, peaks, stats, steps, facts)
    out = readings(watch, reqs, stats, facts)
    out["end_to_end"] = {m["name"]: manifest.reader(m["name"])(rec)
                         for m in cell.end_to_end if m["name"] != "setup_s"}
    out["end_to_end"]["decode.step_ms"] = manifest.reader(
        "decode.step_ms")(rec)
    out["compiles_in_window"] = facts["compiles_in_window"]
    out["failed"] = facts["failed"]
    if trace:
        files = sorted(glob.glob(str(TRACE_DIR / "plugins" / "profile" /
                                     "*" / "*.xplane.pb")))
        device_events, host_spans = load(files[-1])
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        summary = reduce_events(
            device_events,
            [x for x in host_spans if not x[0].startswith(ENGINE_PREFIX)])
        idle = idle_by_span(device_events, host_spans)
        quanta = sum(s.quanta for s in rec.traced_steps())
        out["device.idle_share"] = 100.0 * (1 - summary.busy_s /
                                            summary.window_s)
        out["idle_s"] = idle
        out["traced_quanta"] = quanta
        out["engine.idle_ms_per_quantum"] = idle_ms_per_quantum(idle, quanta)
    return out


def main(argv=None) -> int:
    sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
    from bench.run import NoDevice, chips_for, parse_args
    args = parse_args(argv)
    from bench import manifest
    cell = manifest.load_cell(args.workload)
    peaks = manifest.load_json(manifest.HERE / "peaks.json")
    import jax
    from bench.harness import use_checkout_cache
    use_checkout_cache()
    try:
        devices = chips_for(cell, peaks, jax.devices())
    except NoDevice as e:
        print(f"engine_probe: {e}", file=sys.stderr)
        return 2
    out = probe(cell, args.seed, args.seconds, bool(args.trace),
                peaks[devices[0].device_kind], devices)
    out.update(cell=cell.name, seed=args.seed, trace=args.trace)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
