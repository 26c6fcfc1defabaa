"""One run of one cell: set-up, the measured window, the drain, the
check against the reference, and the record the metric readers read.

The window is driven through the program's ``submit()``/``step()``: due
requests are submitted, the server is stepped while it has work, and the
loop sleeps only when nothing is in flight. Each request is timed from
when it was due. A token counts as served when ``step()`` has returned
it, since that is when a server built on this engine could send it.
"""
from __future__ import annotations

import contextlib
import gc
import math
import os
import shutil
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

import jax
import numpy as np

from bench import manifest
from bench.reference import logit_gaps
from bench.serving import build_server, engines

DRAIN_LIMIT_S = 150.0        # past this after the window, a request failed
SAMPLE_TOKENS = 512          # served tokens the check compares, at least
SAMPLE_REQUESTS = (4, 8)     # and this many requests, at least and at most
TRACE_DIR = manifest.CHECKOUT / ".bench_out" / "trace"
CACHE_DIR = manifest.CHECKOUT / ".jax_cache"


def use_checkout_cache() -> None:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``, a
    fixed path, for the program's own helper too, caching every program
    so that a cell's second run compiles nothing; the TPU runtime's logs
    off (they would go to a fixed path under /tmp). Call before anything
    compiles."""
    from repro.launch.compile_cache import enable_compile_cache
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    jax.config.update("jax_compilation_cache_dir", enable_compile_cache())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


class CompileClock:
    """Backend compile seconds (persistent-cache reads included) and
    persistent-cache hits, from JAX's monitoring events, with the time of
    each so compiles inside the window can be counted."""

    def __init__(self):
        self.secs, self.hits = 0.0, 0
        self.events: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.events.append(time.perf_counter())

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
            self.events.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.events if t0 <= t <= t1)


@dataclass
class ReqStat:
    prompt_len: int
    max_new: int
    due: float
    t_first: float | None = None
    t_last: float | None = None
    n_out: int = 0


@dataclass
class StepStat:
    t0: float
    t1: float
    dt: float                 # decode quantum seconds (StepReport.dt)
    quanta: int               # decode quanta run (one per busy tier)
    emitted: int = 0          # tokens served, first tokens included
    decode_tokens: int = 0
    decode_keys: int = 0      # sum over decode tokens of keys attended
    prefill_tokens: int = 0
    prefill_keys: int = 0     # sum over prompt tokens of keys attended
    per_tier: dict = field(default_factory=dict)   # tier -> tokens decoded


@dataclass
class Record:
    """What the metric readers read."""
    cell: str
    chips: int
    config: dict
    shape: dict
    decode_quantum: int
    window_s: float
    peaks: dict
    tiers: tuple = ()         # the router's tiers; none for one engine
    setup_s: float = 0.0
    setup_compile_s: float = 0.0
    requests: list = field(default_factory=list)
    steps: list = field(default_factory=list)
    trace: object = None
    loop_end: float = 0.0     # when the window's loop (and trace) ended
    drain_end: float = 0.0    # when the drain ended

    def window_steps(self) -> list:
        """Steps that returned inside the window."""
        return [s for s in self.steps if s.t1 <= self.window_s]

    def traced_steps(self) -> list:
        """Steps that ran inside the traced window."""
        return [s for s in self.steps if s.t1 <= self.loop_end]


def _span(name: str, on: bool):
    return jax.profiler.TraceAnnotation(name) if on else \
        contextlib.nullcontext()


def _keys(start: int, k: int) -> int:
    """Keys attended by k consecutive tokens, the first attending start."""
    return k * start + k * (k - 1) // 2


def _step(server, live, reqs, stats, t_ref, traced) -> StepStat:
    before = [len(reqs[j].out) for j in live]
    s0 = time.perf_counter() - t_ref
    with _span("bench.step", traced):
        rep = server.step()
    s1 = time.perf_counter() - t_ref
    reps = rep if isinstance(rep, dict) else {"": rep}
    st = StepStat(t0=s0, t1=s1, dt=sum(r.dt for r in reps.values()),
                  quanta=sum(1 for r in reps.values() if r.dt > 0),
                  per_tier={k: r.decoded for k, r in reps.items()})
    with _span("bench.stamp", traced):
        for j, n0 in zip(live, before):
            n1 = len(reqs[j].out)
            if n1 == n0:
                continue
            rs = stats[j]
            if rs.t_first is None:
                rs.t_first = s1
            rs.t_last = s1
            rs.n_out = n1
            st.emitted += n1 - n0
            if n0 == 0:
                P = rs.prompt_len
                st.prefill_tokens += P
                st.prefill_keys += _keys(1, P)
            start = max(n0, 1)
            k = n1 - start
            st.decode_tokens += k
            st.decode_keys += _keys(rs.prompt_len + start, k)
        live[:] = [j for j in live if not reqs[j].done]
    return st


def serve_window(server, reqs, stats, seconds: float, traced: bool,
                 clock: CompileClock,
                 drain_limit: float = DRAIN_LIMIT_S) -> tuple[list, dict]:
    """Drive the open loop for ``seconds``, then drain for at most
    ``drain_limit`` seconds. Returns the steps and a dict of facts about
    the run."""
    steps: list[StepStat] = []
    live: list[int] = []
    n, i = len(reqs), 0
    late = []
    t_ref = time.perf_counter()
    with _span("bench.window", traced):
        while True:
            now = time.perf_counter() - t_ref
            if i < n and stats[i].due <= now:
                with _span("bench.submit", traced):
                    while i < n and stats[i].due <= now:
                        server.submit(reqs[i])
                        late.append(now - stats[i].due)
                        live.append(i)
                        i += 1
            if now >= seconds:
                break
            if live:
                steps.append(_step(server, live, reqs, stats, t_ref, traced))
            else:
                nxt = min(stats[i].due if i < n else seconds, seconds)
                with _span("bench.wait", traced):
                    time.sleep(max(0.0, nxt - now))
    t_close = time.perf_counter()
    loop_end = t_close - t_ref
    if traced:
        jax.profiler.stop_trace()
    compiles = clock.between(t_ref, t_close)
    unstarted = sum(1 for j in range(i) if stats[j].t_first is None)
    while live and time.perf_counter() - t_close < drain_limit:
        steps.append(_step(server, live, reqs, stats, t_ref, False))
    t_end = time.perf_counter()
    facts = {"compiles_in_window": compiles, "sent": i,
             "unstarted_at_close": unstarted,
             "late_p50_ms": 1e3 * float(np.median(late)) if late else 0.0,
             "late_max_ms": 1e3 * max(late) if late else 0.0,
             "drain_s": t_end - t_close, "loop_end": loop_end,
             "drain_end": t_end - t_ref}
    return steps, facts


def warm_requests(eng, prompt_lens, max_total: int) -> list[int]:
    """Prompt lengths that make the engine compile every program the
    traffic will use: one per prefill bucket, and for the paged kernel
    one per live page-table width the traffic's contexts can reach."""
    from repro.serve.prefill import bucket_len
    bk = lambda n: bucket_len(n, min_bucket=eng.min_bucket,  # noqa: E731
                              max_bucket=eng.max_len)
    need = sorted({bk(n) for n in prompt_lens})
    lens = []
    if eng.paged and eng.paged_kernel:
        q, ps = eng.quantum_tokens, eng.page_size
        lo = -(-(min(prompt_lens) + q) // ps)
        hi = -(-min(max_total + q, eng.max_len) // ps)
        p = 8                  # the engine's narrowest live width
        while p < 2 * hi and p <= eng.pages_per_slot:
            if p >= lo:        # a context that needs between p/2 and p pages
                lens.append(3 * p * ps // 4 - q)
            p *= 2
    covered = {bk(n) for n in lens}
    for b in need:
        if b not in covered:
            lens.append(max(n for n in prompt_lens if bk(n) == b))
    return sorted(lens)


def warm_up(engs, lens, vocab: int) -> None:
    """Serve one request of each length alone on every engine, the
    engines side by side, one thread each: a prefill and one decode
    quantum each."""
    from repro.serve.engine import Request

    def warm(eng):
        rng = np.random.default_rng(0)
        for k, n in enumerate(lens):
            eng.submit(Request(rid=-1 - k,
                               prompt=rng.integers(0, vocab, n).tolist(),
                               max_new=eng.quantum_tokens + 1))
            while eng.has_work():
                eng.step()
    with ThreadPoolExecutor(len(engs)) as pool:
        for f in [pool.submit(warm, eng) for eng in engs]:
            f.result()


def sample(reqs, seed: int) -> list[int]:
    """Finished requests to check: the longest, then others in an order
    drawn from the seed until enough served tokens are covered."""
    done = [j for j, r in enumerate(reqs) if r.done and len(r.out) > 1]
    if not done:
        return []
    longest = max(done, key=lambda j: (len(reqs[j].out), -j))
    rest = [j for j in done if j != longest]
    order = np.random.default_rng(seed + 1).permutation(len(rest))
    pick, tokens = [longest], len(reqs[longest].out)
    for o in order:
        if len(pick) >= SAMPLE_REQUESTS[1] or (
                tokens >= SAMPLE_TOKENS and len(pick) >= SAMPLE_REQUESTS[0]):
            break
        pick.append(rest[o])
        tokens += len(reqs[rest[o]].out)
    return pick


def peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


def prepare(cell: manifest.Cell, seed: int, seconds: float, devices,
            mix: dict | None = None):
    """Build the server over the seed's weights, one replica on each of
    ``devices``, make the window's requests, and warm every program they
    will use."""
    model = manifest.model(cell.config)
    shape = model.model_shape(cell.config)
    server = build_server(model, cell.config, seed, devices)
    mix = mix or cell.traffic
    gen = manifest.generator(mix["kind"])
    arrivals = gen.generate(mix, seconds, seed, shape["vocab_size"])
    eng = engines(server)[0]
    plens = [len(a.prompt) for a in arrivals]
    max_total = max(len(a.prompt) + a.max_new for a in arrivals)
    warm_up(engines(server), warm_requests(eng, plens, max_total),
            shape["vocab_size"])
    return server, arrivals


def measure(server, arrivals, seconds: float, traced: bool,
            clock: CompileClock, drain_limit: float = DRAIN_LIMIT_S):
    """The window and its drain. Returns requests, their stats, the
    steps and the facts of the run."""
    from repro.serve.engine import Request
    reqs = [Request(rid=k, prompt=a.prompt, max_new=a.max_new)
            for k, a in enumerate(arrivals)]
    stats = [ReqStat(len(a.prompt), a.max_new, a.due) for a in arrivals]
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    steps, facts = serve_window(server, reqs, stats, seconds, traced, clock,
                                drain_limit)
    facts["failed"] = sum(1 for r, st in zip(reqs, stats)
                          if not r.done or len(r.out) != st.max_new)
    return reqs, stats, steps, facts


def record(cell, server, seconds, peaks, stats, steps, facts) -> Record:
    tiers = getattr(server, "tiers", ())
    return Record(cell=cell.name, chips=cell.chips, config=cell.config,
                  shape=manifest.model(cell.config).model_shape(cell.config),
                  decode_quantum=engines(server)[0].decode_quantum,
                  window_s=float(seconds), peaks=peaks,
                  tiers=tuple(t.name for t in tiers), requests=stats,
                  steps=steps, loop_end=facts["loop_end"],
                  drain_end=facts["drain_end"])


def check(config: dict, seed: int, reqs, controls=()) -> dict | None:
    """The reference of the configuration's model over a sample of the
    finished requests."""
    picks = sample(reqs, seed)
    if not picks:
        return None
    model = manifest.model(config)
    t0 = time.perf_counter()
    gaps = logit_gaps(model, model.model_shape(config), seed,
                      [(reqs[j].prompt, list(reqs[j].out)) for j in picks],
                      controls=controls)
    gaps["requests"] = len(picks)
    gaps["seconds"] = time.perf_counter() - t0
    return gaps


def run_cell(cell: manifest.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, peaks: dict, devices) -> dict:
    """A whole run after the device check. Returns the result object."""
    clock = CompileClock()
    server, arrivals = prepare(cell, seed, seconds, devices)
    setup_compile_s, hits = clock.secs, clock.hits
    setup_s = time.perf_counter() - t_start
    reqs, stats, steps, facts = measure(server, arrivals, seconds, trace,
                                        clock)
    rec = record(cell, server, seconds, peaks, stats, steps, facts)
    rec.setup_s, rec.setup_compile_s = setup_s, setup_compile_s
    mem = peak_bytes(devices)
    print(f"[bench] setup {setup_s:.3f} s (compile {setup_compile_s:.3f} "
          f"s, cache hits {hits}); compiles in window: "
          f"{facts['compiles_in_window']}; sent {facts['sent']}; generator "
          f"late p50 {facts['late_p50_ms']:.3f} ms, max "
          f"{facts['late_max_ms']:.3f} ms; drain {facts['drain_s']:.3f} s",
          file=sys.stderr)
    del server
    gc.collect()
    if trace:
        from bench.trace import summarize
        rec.trace = summarize(TRACE_DIR)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    gaps = check(cell.config, seed, reqs)
    gap = gaps["program"] if gaps else math.inf
    if gaps:
        print(f"[bench] reference over {gaps['requests']} requests, "
              f"{gaps['positions']} served tokens: {gaps['seconds']:.3f} s",
              file=sys.stderr)
    # widest gap a sound run may show: the configuration file holds it,
    # set from the readings that PERF.md gives
    limit = float(cell.config["limits"]["max_logit_gap"])
    failed = facts["failed"]
    checks = {"max_logit_gap": {"value": gap, "limit": limit},
              "requests_failed": {"value": failed, "limit": 0}}
    correct = bool(gap <= limit and failed == 0)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = manifest.reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips, "memory_peak_bytes": mem}
    out = {"correct": correct, "attempted": len(reqs), "failed": failed,
           "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s
        out["breakdown"] = {"device_ops": rec.trace.device_ops[:10],
                            "idle_gaps": rec.trace.idle_gaps[:10]}
    out["checks"] = checks
    for name, c in checks.items():
        print(f"[check] {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    return out
