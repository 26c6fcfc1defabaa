"""Continuous-batching serving engine.

Slot-based: a fixed decode batch of `max_slots` sequences; finished slots
are refilled by prefilling pending requests and inserting their caches at
the slot index. Admission control follows the paper's scheduling law: the
accelerator class is the fused decode quantum (fixed `S_f`), prefill
admission is the adaptive `S_c` side, driven by the measured
prefill:decode *token* throughput ratio `f` (so a long prompt backlog
can't starve decode, and vice versa).

Fast path (default; DESIGN.md §"Serving fast path"):
  * decode runs `decode_quantum` tokens per dispatch via a jitted
    `lax.scan` with on-device argmax and per-slot done masking — exactly
    one blocking host fetch per quantum (tokens, masks and the post-quantum
    active vector come back as a single packed array);
  * the KV cache and (tokens, pos, active, remaining) state vectors stay
    resident on device and are *donated* through the decode loop, so a
    decode step updates the cache in place instead of allocating a new one;
  * prompts are padded to power-of-2 length buckets and prefilled batched
    (fixed batch `prefill_batch`), then inserted with a single gather-based
    scatter — one XLA compile per bucket, one dispatch per admitted group.

Paged KV cache (`paged=True`; DESIGN.md §5 "Paged KV cache"): full-attention
cache leaves live in a shared page pool `(num_pages, page_size, …)` indexed
through a per-slot page table, with a host-side free-list allocator — pages
are granted at admission, topped up ahead of each decode quantum, and
recycled when a request completes, so short requests stop stranding
max_len-sized cache rows. Ring and mamba layers keep their dense layouts.
Paged decode attention runs the Pallas paged flash-decode kernel by default
(`paged_kernel=True`; `kernels/paged_attention`): the page table is indexed
*in-kernel* and the engine hands the decode loop only the table's *live*
page-column prefix (bucketed to powers of two to bound recompiles), so
per-token attention cost scales with actual context instead of the table
width `max_len/page_size`. `paged_kernel=False` pins the jnp gathered-view
implementation at full table width — the PR 2 cost model — as the escape
hatch.

Sampling: `temperature=0` (default) is greedy argmax; `temperature>0`
enables on-device temperature/top-k/top-p categorical sampling with the
PRNG key carried through the decode scan (still exactly one host sync per
quantum).

Speculative decoding (`draft_cfg=` + `spec_k=`; DESIGN.md §7): a little
draft model proposes spec_k tokens per round inside the decode quantum and
the big target verifies all spec_k+1 positions in ONE batched pass —
the model-level analogue of the paper's little-cores-assist-big-accelerator
split. Greedy traffic is token-identical to target-only decoding; sampled
traffic is distribution-preserving (rejection sampling). The engine carries
a combined {"tgt", "dft"} cache, accounts *accepted* tokens per quantum
(`StepReport.accepted/proposed`), and its measured tok/s is therefore
acceptance-scaled — exactly the effective-throughput signal the
multi-tier routing law wants.

`fast=False` keeps the original per-token / per-prompt reference path; the
benchmark (benchmarks/bench_serve.py) and the equivalence tests in
tests/test_serve.py run both.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.base import ModelConfig
from repro.core.chunking import cpu_chunk
from repro.kernels.paged_attention import ops as paged_ops
from repro.core.tracker import ThroughputTracker
from repro.models.model import model_defs
from repro.models.transformer import layer_schedule
from repro.serve.decode import _sample_tokens, decode_loop_fn, decode_step
from repro.serve.kv_cache import cache_defs, cache_kinds, paged_cache_defs
from repro.serve.prefill import bucket_len, prefill
from repro.sharding import params as prm
from repro.sharding.axes import ShardCtx, mesh_axis_size


class PromptTooLongError(ValueError):
    """Raised at ``submit()`` for a prompt the engine can never schedule.

    A prompt of ``n`` tokens needs at least one decode slot after prefill,
    so ``n`` must be strictly less than the engine's ``max_len``. Raised
    eagerly at submission (not mid-serve) so callers can route the request
    to a longer-context engine — ``MultiEngine`` checks every tier before
    accepting. Subclasses :class:`ValueError`.
    """


class EngineStallError(RuntimeError):
    """``run()``/``drain()`` made no forward progress for far longer than
    the outstanding workload warrants.

    The cycle guard is proportional to queued work (one admission cycle
    per request plus ``max_new / decode_quantum`` decode cycles, with 8×
    slack — see ``Engine._guard_limit``), so this indicates a scheduling
    bug or slot/pool starvation rather than a slow model. The message
    reports pending and unfinished request counts; ``MultiEngine`` raises
    it with per-tier diagnostics *after* reclaiming every tier's slots and
    pages (failure hygiene — DESIGN.md §8), so catching it leaves a clean,
    reusable pool. Subclasses :class:`RuntimeError`.
    """


class RequestFailedError(RuntimeError):
    """Terminal per-request failure: the request exhausted its retry
    budget (or the pool stalled) and was dead-lettered instead of being
    retried forever.

    Never raised out of ``MultiEngine.run`` — a sick request must not
    poison the pool or abort its batch-mates. Instead the pool records an
    instance in ``MultiEngine.dead_letters[rid]`` and stops tracking the
    request; ``Request.done`` stays False and ``Request.out`` holds
    whatever prefix was emitted before the final failure. Subclasses
    :class:`RuntimeError`.
    """


def worst_case_pages(prompt_len: int, max_new: int, decode_quantum: int,
                     max_len: int, page_size: int) -> int:
    """Worst-case pages a request can ever be granted: its context can reach
    prompt+max_new-1, plus quantum-granularity slack for the frozen-slot
    scribble positions, all capped at max_len. Shared with the benchmark's
    pool sizing so the two can't drift."""
    end = min(prompt_len + max_new - 1 + decode_quantum, max_len)
    return max(1, -(-end // page_size))


def _host_fetch(x) -> np.ndarray:
    """Every device→host read on the fast path goes through here, so tests
    can monkeypatch it as a fetch-count probe (one call per decode quantum,
    one per admitted prefill group)."""
    return np.asarray(x)


@dataclass
class Request:
    """One generation request.

    Attributes:
      rid: caller-chosen id (engines never interpret it; benchmarks and
        multi-tier routing logs key on it).
      prompt: token ids to prefill. Must be non-empty and shorter than the
        serving engine's ``max_len``.
      max_new: decode budget — the stream stops after this many generated
        tokens (the first is sampled at prefill), at EOS, or at the
        context limit, whichever comes first.
      out: generated token ids, appended as quanta complete.
      done: set by the engine when the stream is finished.
    """
    rid: int
    prompt: list[int]
    max_new: int = 16
    out: list[int] = field(default_factory=list)
    done: bool = False


@dataclass
class StepReport:
    """What one engine cycle did — the tier-facing throughput surface.

    ``MultiEngine`` feeds ``(decoded, dt)`` of warm cycles into the shared
    cross-tier :class:`~repro.core.tracker.ThroughputTracker`, which is
    what the routing law measures per-tier tok/s from; single-engine
    callers are free to ignore the return value (PR ≤ 3 behaviour).

    Attributes:
      admitted: requests moved from pending into slots this cycle.
      decoded: decode tokens *emitted* across all slots this cycle. For a
        speculative engine one scan round can emit up to spec_k+1 tokens;
        `decoded` counts emissions (acceptance-scaled), never rounds, so
        `decoded / dt` is the *effective* tok/s the routing law should see
        and multi-token steps cannot inflate it.
      dt: wall seconds of the decode quantum dispatch (device interval;
        host-side bookkeeping excluded).
      warm: False when the quantum triggered a fresh XLA compile — such
        intervals measure the compiler, not the tier, and must not be fed
        to a throughput tracker.
      accepted: draft proposals the target verifier accepted this cycle
        (0 for non-speculative engines).
      proposed: draft proposals made this cycle (spec_k per active round);
        accepted/proposed is the acceptance rate.
    """
    admitted: int = 0
    decoded: int = 0
    dt: float = 0.0
    warm: bool = True
    accepted: int = 0
    proposed: int = 0


def _jit_cache_size(fn) -> int:
    """Compile-count probe: distinct traced signatures of a jitted fn."""
    try:
        return int(fn._cache_size())
    except Exception:
        return -1


class PageAllocator:
    """Host-side free-list allocator over the shared KV page pool.

    Page 0 is a reserved scratch ("trash") page: page-table rows of empty
    slots point at it, so the masked scribbles of inactive decode rows can
    never touch a live page. Admission reserves a worst-case page budget
    (`commit`) per request up front; pages are physically handed out lazily
    (`grow_to`) as the context crosses page boundaries. The invariant
    `sum(committed - count) <= len(free)` makes every grow_to infallible —
    pool pressure surfaces only as admission backpressure (`can_commit`).
    """

    def __init__(self, num_pages: int, max_slots: int, pages_per_slot: int):
        if num_pages - 1 < pages_per_slot:
            raise ValueError(
                f"pool of {num_pages} pages (1 reserved) cannot hold one "
                f"full {pages_per_slot}-page context")
        self.num_pages = num_pages
        self.free = list(range(num_pages - 1, 0, -1))   # pop() → low pages
        self.table = np.zeros((max_slots, pages_per_slot), np.int32)
        self.count = np.zeros(max_slots, np.int32)      # pages held per slot
        self.committed = np.zeros(max_slots, np.int32)  # worst-case budget
        self.min_free = len(self.free)                  # high-water telemetry
        self.total_grants = 0                           # page reuse evidence

    @property
    def usable_pages(self) -> int:
        return self.num_pages - 1

    def outstanding(self) -> int:
        """Pages promised to live slots but not yet handed out."""
        return int((self.committed - self.count).sum())

    def can_commit(self, n_pages: int) -> bool:
        return len(self.free) - self.outstanding() >= n_pages

    def commit(self, slot: int, n_pages: int) -> None:
        if self.committed[slot] or self.count[slot]:
            raise RuntimeError(f"slot {slot} already holds pages")
        if not self.can_commit(n_pages):
            raise RuntimeError(
                f"admitted past pool capacity ({n_pages} pages, "
                f"{len(self.free)} free, {self.outstanding()} outstanding)")
        self.committed[slot] = n_pages

    def grow_to(self, slot: int, n_pages: int) -> None:
        if n_pages > self.committed[slot]:
            raise RuntimeError(
                f"slot {slot}: grant of {n_pages} pages exceeds the "
                f"committed budget {int(self.committed[slot])}")
        while self.count[slot] < n_pages:
            self.table[slot, self.count[slot]] = self.free.pop()
            self.count[slot] += 1
            self.total_grants += 1
        self.min_free = min(self.min_free, len(self.free))

    def release(self, slot: int) -> None:
        for t in range(int(self.count[slot])):
            self.free.append(int(self.table[slot, t]))
        self.table[slot, :] = 0                         # back to trash page
        self.count[slot] = 0
        self.committed[slot] = 0

    def check(self) -> None:
        """Pool conservation invariant: every usable page is exactly once
        either on the free list or held by exactly one slot — no leaks,
        no double-frees, no aliased grants. Raises :class:`RuntimeError`
        naming the offending pages. Cheap (host ints); the fault-injection
        suite asserts it after every drain/abort, and callers recovering
        from a tier failure may call it before reusing the engine."""
        held = [int(self.table[s, t])
                for s in range(self.table.shape[0])
                for t in range(int(self.count[s]))]
        seen = sorted(self.free + held)
        want = list(range(1, self.num_pages))
        if seen != want:
            from collections import Counter
            c = Counter(seen)
            dup = sorted(p for p, k in c.items() if k > 1)
            lost = sorted(set(want) - set(c))
            bad = sorted(set(seen) - set(want))
            raise RuntimeError(
                f"page pool invariant violated: leaked={lost} "
                f"double-held={dup} out-of-range={bad}")
        if any(self.count[s] > self.committed[s]
               for s in range(len(self.count))):
            raise RuntimeError(
                f"page pool invariant violated: a slot holds more pages "
                f"than its commit (count={self.count.tolist()}, "
                f"committed={self.committed.tolist()})")


class Engine:
    def __init__(self, cfg: ModelConfig, params, ctx: ShardCtx, *,
                 max_slots: int = 4, max_len: int = 128, eos_id: int = -1,
                 decode_quantum: int = 8, prefill_batch: int | None = None,
                 min_bucket: int = 16, fast: bool = True,
                 paged: bool = False, page_size: int = 16,
                 num_pages: int | None = None, paged_kernel=True,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 0.0, sample_seed: int = 0,
                 draft_cfg: ModelConfig | None = None, draft_params=None,
                 spec_k: int = 0, step_deadline_s: float | None = None):
        """Build a serving engine over an existing parameter tree.

        Args:
          cfg: model config (any decoder-only family; enc-dec audio serves
            through ``whisper_decode_step`` instead).
          params: parameter tree of ``model_defs(cfg)`` on ``ctx``'s mesh
            (``make_engine`` draws it there with
            ``prm.materialize_sharded``) — may be shared (read-only)
            across engines on one mesh, which is how ``MultiEngine``
            builds token-equivalent tiers.
          ctx: sharding context; the KV cache is mesh-placed at init.
          max_slots: decode batch width — concurrent streams.
          max_len: per-slot context capacity (prompt + generated tokens).
            Prompts must be strictly shorter (``PromptTooLongError``).
          eos_id: token id that ends a stream (-1: never).
          decode_quantum: tokens decoded per fused dispatch; the host syncs
            exactly once per quantum. Also the fixed accelerator chunk
            ``S_f`` of the HBB admission law.
          prefill_batch: rows per batched prefill dispatch (default
            ``max_slots``).
          min_bucket: smallest power-of-2 prompt-length bucket; one XLA
            compile per bucket, not per distinct prompt length.
          fast: False pins the original per-token reference path (greedy
            only; baselines and equivalence tests).
          paged: serve full-attention KV from a shared page pool with a
            per-slot page table instead of dense ``max_slots × max_len``
            rows (DESIGN.md §5). Requires ``fast=True`` and an unsharded
            batch axis; rings/mamba state stay dense either way.
          page_size: tokens per KV page; must divide ``max_len`` and be a
            multiple of the model-axis size.
          num_pages: pool size including the reserved trash page 0
            (default: enough for every slot at full ``max_len``). Sizing
            it *below* the worst case is the point — admission exerts
            backpressure instead of stranding HBM.
          paged_kernel: True (default) walks the page table *in-kernel*
            (Pallas on TPU, the fused blockwise reference on CPU) so
            decode cost follows live context; False pins the jnp
            gathered-view escape hatch at full table width (the PR 2 cost
            model / equivalence oracle). A string names a
            ``kernels/paged_attention`` impl explicitly (e.g.
            ``"interpret"``).
          temperature: 0 (default) decodes greedy argmax; > 0 samples a
            temperature-scaled categorical on device (PRNG key rides the
            decode scan carry — still one host sync per quantum).
          top_k: truncate sampling to the k most likely tokens (0: off;
            1 collapses to greedy regardless of seed).
          top_p: nucleus sampling — truncate to the smallest token set
            whose probability mass reaches top_p (0 or 1.0: off, and
            traces to the identical jaxpr as the pre-nucleus sampler).
          sample_seed: PRNG seed for sampling; same seed → same streams.
          draft_cfg: little proposal model for speculative decoding
            (None: off). Must be decoder-only, full-attention with no
            sliding window (its dense cache is written optimistically and
            stale rows must stay invalid until overwritten), and share the
            target's vocab. Requires ``fast=True``.
          draft_params: the draft's parameter tree; None materializes
            fresh ones from ``draft_cfg`` (tests / toy tiers —
            ``models/draft.py`` builds an aligned big/little pair from the
            target's own weights).
          spec_k: draft proposals per verify round (≥ 1 with a draft).
            Each decode-scan round emits between 1 and spec_k+1 tokens;
            greedy output is token-identical to ``spec_k=0`` serving.
          step_deadline_s: advisory wall-clock budget for one ``step()``
            (None: unbounded). The engine itself never preempts a quantum
            — XLA dispatches are not interruptible — but a supervisor
            (``MultiEngine``'s per-tier watchdog, DESIGN.md §8) reads this
            to decide when a step has hung and the tier should be
            quarantined.
        """
        assert not cfg.enc_dec, "enc-dec serving uses whisper_decode_step"
        self.cfg, self.params, self.ctx = cfg, params, ctx
        self.max_slots, self.max_len, self.eos_id = max_slots, max_len, eos_id
        self.fast = fast
        if step_deadline_s is not None and step_deadline_s <= 0:
            raise ValueError(f"step_deadline_s must be positive or None, "
                             f"got {step_deadline_s}")
        self.step_deadline_s = step_deadline_s
        self.decode_quantum = max(1, decode_quantum)
        if temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {temperature}")
        if not 0 <= top_k <= cfg.vocab:
            raise ValueError(f"top_k must be in [0, vocab={cfg.vocab}], "
                             f"got {top_k}")
        if temperature and not fast:
            raise ValueError("sampling (temperature > 0) requires fast=True "
                             "— the legacy reference path is greedy only")
        if not 0.0 <= top_p <= 1.0:
            raise ValueError(f"top_p must be in [0, 1], got {top_p}")
        self.temperature, self.top_k = float(temperature), int(top_k)
        self.top_p = float(top_p)
        # ---- speculative decode (draft/verify) validation ----------------
        self._spec = draft_cfg is not None
        if spec_k and not self._spec:
            raise ValueError("spec_k requires a draft_cfg")
        self.spec_k = int(spec_k)
        self.draft_cfg = draft_cfg
        self.tokens_per_step = (self.spec_k + 1) if self._spec else 1
        if self._spec:
            if not fast:
                raise ValueError("speculative decode requires fast=True")
            if spec_k < 1:
                raise ValueError(f"spec_k must be >= 1 with a draft, got "
                                 f"{spec_k}")
            if draft_cfg.enc_dec:
                raise ValueError("draft must be decoder-only")
            if draft_cfg.vocab != cfg.vocab:
                raise ValueError(
                    f"draft vocab {draft_cfg.vocab} != target vocab "
                    f"{cfg.vocab} — proposals must be target token ids")
            for seg in layer_schedule(draft_cfg):
                for bc in seg.pattern:
                    if bc.mixer != "attn" or bc.window:
                        raise ValueError(
                            "draft must be full-attention with no sliding "
                            "window: its cache rows are written "
                            "optimistically, which is only sound when "
                            "validity is gpos <= pos on a dense cache")
            windows = [bc.window for seg in layer_schedule(cfg)
                       for bc in seg.pattern
                       if bc.mixer == "attn" and bc.window]
            if windows and min(windows) < spec_k + 1:
                raise ValueError(
                    f"spec_k+1 = {spec_k + 1} verify rows exceed the "
                    f"target's smallest window {min(windows)} — staged "
                    f"rows must all be in-window for every verify query")
        self.spec_accepted = 0                 # lifetime acceptance counters
        self.spec_proposed = 0
        if isinstance(paged_kernel, (bool, int)):
            paged_kernel = bool(paged_kernel)   # 0/1 → canonical bools
        elif paged_kernel not in paged_ops._IMPLS:
            raise ValueError(
                f"paged_kernel must be a bool or one of {paged_ops._IMPLS}, "
                f"got {paged_kernel!r}")
        self.paged_kernel = paged_kernel
        self.prefill_batch = prefill_batch or max_slots
        self.min_bucket = min_bucket
        # padded buckets are only sound when every mixer is attention —
        # a mamba state scan would absorb the pad tokens (DESIGN.md)
        self.pad_safe = all(bc.mixer == "attn"
                            for seg in layer_schedule(cfg)
                            for bc in seg.pattern)
        msize = ctx.axis_size("model")
        # host-made state goes straight to this engine's mesh (one replica
        # per chip must not route its vectors through the default device)
        self._repl = NamedSharding(ctx.mesh, PartitionSpec())
        self.paged = bool(paged)
        cache_d = None
        if self.paged:
            if not fast:
                raise ValueError("paged KV cache requires fast=True")
            if mesh_axis_size(ctx.mesh, ("pod", "data")) > 1:
                # pool leaves are replicated over the batch axes but written
                # per-slot under check_rep=False — replicas would silently
                # diverge; data-parallel paged pools are a ROADMAP follow-on
                raise ValueError("paged KV cache requires an unsharded "
                                 "batch axis (data/pod mesh size 1)")
            if page_size <= 0 or page_size % msize:
                raise ValueError(
                    f"page_size {page_size} must be a positive multiple of "
                    f"the model-axis size {msize}")
            if max_len % page_size:
                raise ValueError(
                    f"max_len {max_len} must be a multiple of page_size "
                    f"{page_size}")
            self.page_size = page_size
            self.pages_per_slot = max_len // page_size
            self.num_pages = num_pages or 1 + max_slots * self.pages_per_slot
            self.alloc = PageAllocator(self.num_pages, max_slots,
                                       self.pages_per_slot)
            cache_d = paged_cache_defs(cfg, max_slots, max_len, msize,
                                       num_pages=self.num_pages,
                                       page_size=page_size)
            self.page_table_dev = jax.device_put(self.alloc.table,
                                                 self._repl)
            self._table_dirty = False
            self.pos_host = np.zeros(max_slots, np.int64)  # device-pos mirror
        else:
            cache_d = cache_defs(cfg, max_slots, max_len, msize)
        if self._spec:
            # combined tree: the draft always serves from a dense cache
            # (optimistic writes are only sound there — see above)
            cache_d = {"tgt": cache_d,
                       "dft": cache_defs(draft_cfg, max_slots, max_len,
                                         msize)}
        # the cache is born on the mesh in its final sharding: the donated
        # decode loop emits mesh-sharded leaves, and a cache placed any
        # other way would make every admit bucket compile twice (once per
        # sharding). Real meshes get the defs' kv_seq shardings
        # (replicating a pool across the model axis would forfeit the HBM
        # the pool saves)
        self.cache = prm.materialize_sharded(cache_d, jax.random.PRNGKey(0),
                                             ctx)
        self.kinds = cache_kinds(cfg, paged=self.paged)
        if self._spec:
            if draft_params is None:
                draft_params = prm.materialize_sharded(
                    model_defs(draft_cfg), jax.random.PRNGKey(0), ctx)
            self.draft_params = draft_params
            self.kinds = {"tgt": self.kinds,
                          "dft": cache_kinds(draft_cfg, paged=False)}
            self._loop_params = {"tgt": params, "dft": draft_params}
        else:
            self.draft_params = None
            self._loop_params = params
        self.pos = np.zeros(max_slots, np.int32)       # legacy-path mirror
        self.slot_req: list[Optional[Request]] = [None] * max_slots
        self.pending: list[Request] = []
        self.tracker = ThroughputTracker(
            {"decode": "accelerator", "prefill": "core"}, f0=2.0)
        self.cycle_log: list[dict] = []                # per-cycle balance
        self._last_admitted = 0
        self.quanta = 0                                # decode dispatches
        self.prefill_groups = 0                        # prefill dispatches
        # device-resident decode state (fast path), mesh-placed like cache
        repl = self._repl
        self.tokens_dev = jax.device_put(jnp.zeros(max_slots, jnp.int32),
                                         repl)
        self.pos_dev = jax.device_put(jnp.zeros(max_slots, jnp.int32), repl)
        self.active_dev = jax.device_put(jnp.zeros(max_slots, bool), repl)
        self.remaining_dev = jax.device_put(jnp.zeros(max_slots, jnp.int32),
                                            repl)
        self.rng_dev = jax.device_put(jax.random.PRNGKey(sample_seed), repl)
        # independent stream for first-token sampling at prefill (split per
        # admitted group on host — a device op, not a blocking fetch)
        self._prefill_rng = jax.device_put(
            jax.random.PRNGKey(sample_seed + 1), repl)
        # ---- jitted cells -------------------------------------------------
        self._decode = jax.jit(
            lambda p, c, t, pos: decode_step(cfg, p, c, t, pos, ctx))
        self._prefill = jax.jit(
            lambda p, t: prefill(cfg, p, t, ctx, max_len=max_len))
        self._insert = jax.jit(self._insert_impl, donate_argnums=(0,))
        self._decode_loop = jax.jit(
            decode_loop_fn(cfg, ctx, num_steps=self.decode_quantum,
                           eos_id=eos_id, max_len=max_len, paged=self.paged,
                           paged_kernel=self.paged_kernel,
                           temperature=self.temperature, top_k=self.top_k,
                           top_p=self.top_p, draft_cfg=draft_cfg,
                           spec_k=self.spec_k),
            donate_argnums=(1, 2, 3, 4, 5, 6))
        self._prefill_fast = jax.jit(self._prefill_fast_impl)
        self._admit = jax.jit(
            self._admit_paged_impl if self.paged else self._admit_impl,
            donate_argnums=(0, 1, 2, 3, 4))

    # ---- cache slot insertion (jitted scatter on the batch dim) ----------
    def _insert_impl(self, cache, one_cache, slot):
        # cache leaves are (repeat, batch, …) — batch is axis 1
        def ins(c, o):
            return jax.lax.dynamic_update_slice_in_dim(c, o.astype(c.dtype),
                                                       slot, 1)
        return jax.tree.map(ins, cache, one_cache)

    # ---- fast path: batched prefill + fused admission --------------------
    def _prefill_fast_impl(self, params, toks, prompt_len, key):
        """(P,Sb) padded prompts → (first sampled token (P,), batched
        cache). Sampling (greedy at temperature=0) happens on device so
        admission never ships logits home — the first token of a stream
        follows the same temperature/top-k/top-p law as the decode loop.
        Speculative engines prefill the draft too (its logits are unused;
        only its cache matters) and return the combined tree."""
        tp = params["tgt"] if self._spec else params
        logits, cache = prefill(self.cfg, tp, toks, self.ctx,
                                max_len=self.max_len, prompt_len=prompt_len,
                                page_size=(self.page_size if self.paged
                                           else None))
        if self._spec:
            _, dcache = prefill(self.draft_cfg, params["dft"], toks,
                                self.ctx, max_len=self.max_len,
                                prompt_len=prompt_len)
            cache = {"tgt": cache, "dft": dcache}
        first = _sample_tokens(logits, key, temperature=self.temperature,
                               top_k=self.top_k, top_p=self.top_p)
        return first, cache

    def _admit_state(self, tokens, pos, active, remaining, hit, idx,
                     first, prompt_len, max_new):
        """Blend the prefilled rows' scalar state into the slot vectors."""
        pl = jnp.take(prompt_len, idx)
        rem = jnp.take(max_new, idx) - 1       # prefill already emitted one
        tokens = jnp.where(hit, jnp.take(first, idx), tokens)
        pos = jnp.where(hit, pl, pos)
        remaining = jnp.where(hit, rem, remaining)
        # pl == max_len-1 still gets one decode step (writes the last cache
        # slot) — matches the legacy path's post-step done check
        active = jnp.where(hit, (rem > 0) & (pl < self.max_len), active)
        return tokens, pos, active, remaining

    def _admit_sel(self, slots, valid):
        """slot-targeting mask/index pair for the gather-formulated scatter:
        for each engine slot s, the (at most one) prefill row targeting s."""
        S = self.max_slots
        sel = valid[None, :] & (slots[None, :] == jnp.arange(S)[:, None])
        return sel.any(axis=1), jnp.argmax(sel, axis=1)

    def _admit_impl(self, cache, tokens, pos, active, remaining, new_cache,
                    first, prompt_len, max_new, slots, valid):
        """Scatter a prefilled batch into its engine slots in ONE dispatch.

        Formulated as a gather so it stays shape-stable under jit: for each
        engine slot s, pick the (at most one) prefill row targeting s and
        blend it into every cache leaf / state vector.
        """
        S = self.max_slots
        hit, idx = self._admit_sel(slots, valid)

        def ins(c, o):
            g = jnp.take(o, idx, axis=1)       # (repeat, S, …)
            m = hit.reshape((1, S) + (1,) * (c.ndim - 2))
            return jnp.where(m, g.astype(c.dtype), c)

        cache = jax.tree.map(ins, cache, new_cache)
        return (cache,) + self._admit_state(tokens, pos, active, remaining,
                                            hit, idx, first, prompt_len,
                                            max_new)

    def _admit_paged_impl(self, cache, tokens, pos, active, remaining,
                          new_cache, first, prompt_len, max_new, slots,
                          valid, page_src):
        """Paged admit: dense leaves (rings, mamba state) blend per slot as
        in `_admit_impl`; pool leaves scatter the bucket-sized prefill rows
        into their freshly allocated pages. `page_src` (num_pages,) int32 is
        host-computed: flat (row · pages_per_row + page) source index for
        each pool page, or -1 for pages this group doesn't touch."""
        S = self.max_slots
        hit, idx = self._admit_sel(slots, valid)

        def ins(kind, c, o):
            if kind == "paged":
                # c (repeat, N, ps, …) pool; o (repeat, P, Tb·ps, …) rows
                ps, N = c.shape[2], c.shape[1]
                rep, Pb = o.shape[0], o.shape[1]
                Tb = o.shape[2] // ps
                src = o.reshape((rep, Pb * Tb, ps) + o.shape[3:])
                g = jnp.take(src, jnp.clip(page_src, 0, Pb * Tb - 1), axis=1)
                m = (page_src >= 0).reshape((1, N) + (1,) * (c.ndim - 2))
                return jnp.where(m, g.astype(c.dtype), c)
            g = jnp.take(o, idx, axis=1)       # (repeat, S, …)
            m = hit.reshape((1, S) + (1,) * (c.ndim - 2))
            return jnp.where(m, g.astype(c.dtype), c)

        cache = jax.tree.map(ins, self.kinds, cache, new_cache)
        return (cache,) + self._admit_state(tokens, pos, active, remaining,
                                            hit, idx, first, prompt_len,
                                            max_new)

    def submit(self, req: Request) -> None:
        n = len(req.prompt)
        if n == 0:
            raise ValueError(f"request {req.rid}: empty prompt")
        if n >= self.max_len:
            raise PromptTooLongError(
                f"request {req.rid}: prompt of {n} tokens needs at least "
                f"one decode slot; engine max_len is {self.max_len}")
        self.pending.append(req)

    def free_slots(self) -> list[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def prefill_compiles(self) -> int:
        """Distinct prefill compiles so far (fast: one per length bucket)."""
        return _jit_cache_size(self._prefill_fast if self.fast
                               else self._prefill)

    def reserved_cache_bytes(self) -> int:
        """Persistently reserved KV-cache HBM (pool + dense leaves)."""
        return sum(int(x.nbytes) for x in jax.tree.leaves(self.cache))

    def decode_hlo(self) -> str:
        """Compiled text of the decode-quantum program at the full page
        table width: shows which kernels the device runs (a Pallas kernel
        appears as ``tpu_custom_call``). Lowers without running or
        donating anything."""
        args = (self._loop_params, self.cache, self.tokens_dev,
                self.pos_dev, self.active_dev, self.remaining_dev,
                self.rng_dev)
        if self.paged:
            args += (self.page_table_dev,)
        return self._decode_loop.lower(*args).compile().as_text()

    # ---- tier-facing interface (submit / step / drain) -------------------
    # MultiEngine treats an Engine as one resource of the paper's CC/FC
    # pool: it probes capacity, hands over queued requests, steps it, and
    # reclaims whatever the engine's own admission law left pending.
    def has_work(self) -> bool:
        """True while any request is pending or occupies a decode slot."""
        return bool(self.pending) or any(r is not None for r in self.slot_req)

    def take_pending(self) -> list[Request]:
        """Hand back the not-yet-admitted queue (admitted requests stay —
        their KV lives in this engine's cache). A multi-tier router calls
        this after each cycle so work an engine could not admit (slot or
        pool backpressure) reroutes instead of queueing behind it."""
        out, self.pending = self.pending, []
        return out

    def plan_admission(self, reqs: list[Request]) -> int:
        """How many of ``reqs`` (a prefix, in order) this engine could admit
        right now: bounded by free slots net of already-pending work and,
        for paged engines, by the pool's worst-case commit budget. Purely
        advisory — submission still goes through ``submit()`` — but it lets
        a router keep work off a tier that cannot take it."""
        n = min(len(reqs), len(self.free_slots()) - len(self.pending))
        if n <= 0:
            return 0
        if not self.paged:
            return n
        # already-pending requests will commit their worst case first —
        # count them against the pool before promising capacity for more
        planned = sum(self._worst_pages(r) for r in self.pending)
        k = 0
        for req in reqs[:n]:
            w = self._worst_pages(req)
            if not self.alloc.can_commit(planned + w):
                break
            planned += w
            k += 1
        return k

    def decode_throughput(self) -> float:
        """EWMA decode tokens/sec this engine has measured for itself (0.0
        until the first warm quantum). The cross-tier router prefers the
        shared tracker it feeds from :class:`StepReport`; this accessor is
        for introspection and examples."""
        return self.tracker.throughput("decode")

    def drain(self) -> None:
        """Step until no pending or admitted work remains (same stall guard
        as ``run()``). Tier-facing shutdown: a router that stops routing to
        this engine can still let admitted streams finish."""
        guard, limit = 0, self._guard_limit()
        while self.has_work():
            if guard >= limit:
                raise EngineStallError(
                    f"drain made no progress after {guard} cycles "
                    f"(limit {limit}): {len(self.pending)} pending")
            self.step()
            guard += 1

    def abort(self) -> list:
        """Failure-safe reclaim of every *admitted* request (DESIGN.md §8).

        Empties the decode slots without stepping the model: each in-flight
        request is handed back with whatever tokens it already emitted
        (``Request.out`` is preserved — the resume-from-emitted retry law
        re-prefills from prompt+out), its pages are released, and the
        device-side active/remaining vectors are zeroed so a later admit
        meets the same inactive slots a fresh engine has. The KV cache
        contents are left as-is — inactive slots never read them, dense
        rows are fully overwritten at the next admit, and released pages
        re-enter the free list (table rows point back at trash page 0).

        Host-side bookkeeping only — safe to call even when the engine's
        last ``step()`` raised mid-quantum. Pending (never-admitted)
        requests are NOT included; callers wanting those too should call
        ``take_pending()`` first. Returns the reclaimed requests in slot
        order."""
        out = []
        for i, req in enumerate(self.slot_req):
            if req is None:
                continue
            out.append(req)
            self.slot_req[i] = None
            if self.paged:
                self._release_slot_pages(i)
                self.pos_host[i] = 0
            self.pos[i] = 0                        # legacy-path mirror
        if self.paged:
            self._push_page_table()
        if self.fast:
            self.active_dev = jax.device_put(
                jnp.zeros(self.max_slots, bool), self._repl)
            self.remaining_dev = jax.device_put(
                jnp.zeros(self.max_slots, jnp.int32), self._repl)
        return out

    # ---- paged-pool bookkeeping ------------------------------------------
    @property
    def quantum_tokens(self) -> int:
        """Most tokens one decode quantum can advance a slot: every scan
        round emits up to ``tokens_per_step`` (1, or spec_k+1 for a
        speculative engine). Page grants and the live-table slice budget
        this worst case — acceptance below 100% just leaves slack."""
        return self.decode_quantum * self.tokens_per_step

    def _worst_pages(self, req: Request) -> int:
        return worst_case_pages(len(req.prompt), req.max_new,
                                self.quantum_tokens, self.max_len,
                                self.page_size)

    def _grant_quantum_pages(self, active_slots: list[int]) -> None:
        """Pre-grant every occupied slot enough pages to cover the coming
        quantum, so the decode loop never needs a device-side allocator."""
        for i in active_slots:
            end = min(int(self.pos_host[i]) + self.quantum_tokens,
                      self.max_len)
            target = -(-end // self.page_size)
            if target > self.alloc.count[i]:
                self.alloc.grow_to(i, target)
                self._table_dirty = True

    def _release_slot_pages(self, slot: int) -> None:
        self.alloc.release(slot)
        self._table_dirty = True

    def _push_page_table(self) -> None:
        if self._table_dirty:
            self.page_table_dev = jax.device_put(self.alloc.table,
                                                 self._repl)
            self._table_dirty = False

    def _live_page_table(self, active_slots: list[int]):
        """Page-table view handed to the decode loop. The kernel path gets
        only the *live* column prefix — enough pages to cover every active
        slot through the coming quantum, rounded up to a power of two so the
        loop compiles once per bucket, not once per context length, and
        floored at 8 pages: sub-8 buckets save nothing measurable but
        multiply compiles (an all-short admission wave would mint a fresh
        bucket mid-serve). The gather path keeps the full table (the PR 2
        escape hatch stays byte-identical). Slots whose stale `pos` exceeds
        the sliced width are routed to the trash page by `_paged_write`'s
        range guard."""
        if not self.paged_kernel:
            return self.page_table_dev
        end = max(min(int(self.pos_host[i]) + self.quantum_tokens,
                      self.max_len) for i in active_slots)
        n_live = max(-(-end // self.page_size), 8)
        n_live = min(self.pages_per_slot, 1 << (n_live - 1).bit_length())
        if n_live == self.pages_per_slot:  # full width → no slice dispatch
            return self.page_table_dev
        return self.page_table_dev[:, :n_live]

    # ---- one engine cycle -------------------------------------------------
    def step(self) -> StepReport:
        """One engine cycle: admit pending prompts (HBB token budget), run
        one decode quantum, retire finished slots. Returns a
        :class:`StepReport` so a multi-tier router can measure this
        engine's per-quantum token throughput without reaching into its
        private tracker."""
        if not self.fast:
            return self._step_legacy()
        self._last_admitted = 0
        free = self.free_slots()
        if self.pending and free:
            self._admit_pending(free)
        active_slots = [i for i, r in enumerate(self.slot_req)
                        if r is not None]
        if not active_slots:
            if self._last_admitted:   # everything finished at prefill —
                self.cycle_log.append({"admitted": self._last_admitted,
                                       "decoded": 0,
                                       "f": self.tracker.f()})
            return StepReport(admitted=self._last_admitted)
        if self.paged:
            self._grant_quantum_pages(active_slots)
            self._push_page_table()
        t0 = time.perf_counter()
        n0 = _jit_cache_size(self._decode_loop)
        args = (self._loop_params, self.cache, self.tokens_dev,
                self.pos_dev, self.active_dev, self.remaining_dev,
                self.rng_dev)
        if self.paged:
            carry, packed = self._decode_loop(
                *args, self._live_page_table(active_slots))
        else:
            carry, packed = self._decode_loop(*args)
        (self.cache, self.tokens_dev, self.pos_dev, self.active_dev,
         self.remaining_dev, self.rng_dev) = carry
        packed_h = _host_fetch(packed)         # the ONE host sync per quantum
        dt = time.perf_counter() - t0
        self.quanta += 1
        N = self.decode_quantum
        # a speculative round can emit up to tokens_per_step tokens, so the
        # packed array carries N·K emission rows (round-major, in order)
        NK = N * self.tokens_per_step
        toks_h = packed_h[:NK]
        msks_h = packed_h[NK:2 * NK].astype(bool)
        act_h = packed_h[-1].astype(bool)
        emitted = int(msks_h.sum())
        accepted = proposed = 0
        if self._spec:
            accepted = int(packed_h[2 * NK:2 * NK + N].sum())
            # emission row 0 of each round is exactly "active at round
            # start" — each active round made spec_k proposals
            rounds = int(msks_h.reshape(
                N, self.tokens_per_step, -1)[:, 0, :].sum())
            proposed = self.spec_k * rounds
            self.spec_accepted += accepted
            self.spec_proposed += proposed
        # quanta that just compiled don't measure decode speed — feeding
        # them to the tracker skews the admission f-ratio for many cycles
        # (probe unavailable (-1) → record everything: a slightly skewed f
        # beats a tracker frozen at its prior)
        warm = n0 < 0 or _jit_cache_size(self._decode_loop) == n0
        if emitted and warm:
            # `emitted` counts accepted emissions, never rounds — so this
            # is acceptance-scaled *effective* tok/s (the routing signal)
            self.tracker.record("decode", emitted, dt)
        if self.paged:
            self.pos_host += msks_h.sum(axis=0)
        for q in range(NK):
            row = msks_h[q]
            for i in active_slots:
                if row[i]:
                    self.slot_req[i].out.append(int(toks_h[q, i]))
        for i in active_slots:
            if not act_h[i]:
                self.slot_req[i].done = True
                self.slot_req[i] = None
                if self.paged:
                    self._release_slot_pages(i)
        self.cycle_log.append({"admitted": self._last_admitted,
                               "decoded": emitted, "f": self.tracker.f()})
        return StepReport(admitted=self._last_admitted, decoded=emitted,
                          dt=dt, warm=warm, accepted=accepted,
                          proposed=proposed)

    def _admit_pending(self, free: list[int]) -> None:
        """HBB chunking law over token units: the decode quantum is the
        fixed accelerator chunk (S_f = quantum × slots tokens); the prompt-
        token budget admitted this cycle is the adaptive S_c side. Paged
        engines additionally stop at the pool's worst-case page budget
        (admission backpressure instead of a mid-quantum page fault)."""
        r_tokens = sum(len(q.prompt) for q in self.pending)
        budget = cpu_chunk(S_f=self.quantum_tokens * self.max_slots,
                           f=self.tracker.f(), r=r_tokens, n_cores=1)
        take: list[Request] = []
        planned_pages = 0
        while self.pending and len(take) < len(free):
            req = self.pending[0]
            n = len(req.prompt)
            if take and budget < n:            # always admit ≥ 1
                break
            if self.paged:
                W = self._worst_pages(req)
                if not self.alloc.can_commit(planned_pages + W):
                    break                      # pool backpressure
                planned_pages += W
            budget -= n
            take.append(self.pending.pop(0))
        if not take:
            return
        self._last_admitted = len(take)
        groups: dict[int, list[Request]] = {}
        for req in take:
            b = (bucket_len(len(req.prompt), min_bucket=self.min_bucket,
                            max_bucket=self.max_len)
                 if self.pad_safe else len(req.prompt))
            groups.setdefault(b, []).append(req)
        ptoks = 0
        pdt = 0.0
        for Sb in sorted(groups):
            grp = groups[Sb]
            for k0 in range(0, len(grp), self.prefill_batch):
                chunk = grp[k0:k0 + self.prefill_batch]
                dt, warm = self._prefill_group(Sb, chunk, free)
                if warm:                       # skip compile-tainted samples
                    pdt += dt
                    ptoks += sum(len(q.prompt) for q in chunk)
        # device interval only: host-side packing and the first-token fetch
        # used to ride along and skewed the admission f-ratio low
        if ptoks:
            self.tracker.record("prefill", ptoks, pdt)

    def _prefill_group(self, Sb: int, reqs: list[Request],
                       free: list[int]) -> tuple[float, bool]:
        """Prefill + admit one bucket group; returns (device seconds for the
        prefill dispatch + admit scatter, blocked-until-ready; whether the
        interval is compile-free and thus safe to feed the f-tracker)."""
        # fixed batch for padded buckets (one compile per bucket); smallest
        # power-of-2 batch for exact-length (mamba) groups
        P = (self.prefill_batch if self.pad_safe
             else 1 << (len(reqs) - 1).bit_length())
        toks = np.zeros((P, Sb), np.int32)
        pl = np.ones(P, np.int32)
        mn = np.ones(P, np.int32)
        valid = np.zeros(P, bool)
        slots = np.zeros(P, np.int32)
        for j, req in enumerate(reqs):
            toks[j, :len(req.prompt)] = req.prompt
            pl[j] = len(req.prompt)
            mn[j] = req.max_new
            valid[j] = True
            slots[j] = free.pop(0)
        extra = ()
        if self.paged:
            # step() pushes the updated table to device before the next
            # decode quantum; the admit scatter itself reads page_src only
            extra = (jnp.asarray(self._alloc_group_pages(Sb, reqs, slots)),)
        t0 = time.perf_counter()
        p0 = _jit_cache_size(self._prefill_fast)
        a0 = _jit_cache_size(self._admit)
        self._prefill_rng, sub = jax.random.split(self._prefill_rng)
        first, new_cache = self._prefill_fast(self._loop_params,
                                              jnp.asarray(toks),
                                              jnp.asarray(pl), sub)
        (self.cache, self.tokens_dev, self.pos_dev, self.active_dev,
         self.remaining_dev) = self._admit(
            self.cache, self.tokens_dev, self.pos_dev, self.active_dev,
            self.remaining_dev, new_cache, first, jnp.asarray(pl),
            jnp.asarray(mn), jnp.asarray(slots), jnp.asarray(valid), *extra)
        jax.block_until_ready((first, self.tokens_dev))
        dt = time.perf_counter() - t0
        # probe unavailable (-1 sentinel) → treat as warm and record
        warm = (p0 < 0 or a0 < 0
                or (_jit_cache_size(self._prefill_fast) == p0
                    and _jit_cache_size(self._admit) == a0))
        self.prefill_groups += 1
        first_h = _host_fetch(first)           # one sync per admitted group
        for j, req in enumerate(reqs):
            req.out.append(int(first_h[j]))
            if req.max_new <= 1:
                req.done = True                # budget spent at prefill
                free.insert(0, int(slots[j]))
                if self.paged:
                    self._release_slot_pages(int(slots[j]))
            else:
                self.slot_req[int(slots[j])] = req
                if self.paged:
                    self.pos_host[int(slots[j])] = len(req.prompt)
        return dt, warm

    def _alloc_group_pages(self, Sb: int, reqs: list[Request],
                           slots: np.ndarray) -> np.ndarray:
        """Commit each request's worst-case page budget, hand out the pages
        its prompt needs now, and build the pool-page → prefill-row source
        map the paged admit scatter consumes."""
        ps = self.page_size
        Tb = -(-Sb // ps)                      # pages per bucket row
        page_src = np.full(self.num_pages, -1, np.int32)
        for j, req in enumerate(reqs):
            slot = int(slots[j])
            self.alloc.commit(slot, self._worst_pages(req))
            need = -(-len(req.prompt) // ps)
            self.alloc.grow_to(slot, need)
            self._table_dirty = True
            for t in range(need):
                page_src[self.alloc.table[slot, t]] = j * Tb + t
        return page_src

    # ---- reference slow path (pre-fast-path engine, kept for baselines) --
    def _step_legacy(self) -> StepReport:
        free = self.free_slots()
        admitted = 0
        if self.pending and free:
            r = len(self.pending)
            admit = cpu_chunk(S_f=self.max_slots, f=self.tracker.f(), r=r,
                              n_cores=1)
            admit = max(1, min(admit, len(free), r))
            admitted = admit
            t0 = time.perf_counter()
            for _ in range(admit):
                req = self.pending.pop(0)
                slot = self.free_slots()[0]
                toks = jnp.asarray(req.prompt, jnp.int32)[None]
                logits, one_cache = self._prefill(self.params, toks)
                self.cache = self._insert(self.cache, one_cache,
                                          jnp.int32(slot))
                nxt = int(jnp.argmax(logits[0]))
                req.out.append(nxt)
                if req.max_new <= 1:           # budget spent at prefill
                    req.done = True            # (stream parity w/ fast path)
                    continue
                self.slot_req[slot] = req
                self.pos[slot] = len(req.prompt)
            self.tracker.record("prefill", admit, time.perf_counter() - t0)

        active = [i for i, r in enumerate(self.slot_req) if r is not None]
        if not active:
            return StepReport(admitted=admitted)
        toks = np.zeros(self.max_slots, np.int32)
        for i in active:
            toks[i] = self.slot_req[i].out[-1]
        t0 = time.perf_counter()
        n0 = _jit_cache_size(self._decode)
        logits, self.cache = self._decode(self.params, self.cache,
                                          jnp.asarray(toks),
                                          jnp.asarray(self.pos))
        nxt = np.asarray(jnp.argmax(logits, -1))
        dt = time.perf_counter() - t0
        # compile-tainted intervals must not reach a throughput tracker
        # (StepReport.warm contract — same probe as the fast path)
        warm = n0 < 0 or _jit_cache_size(self._decode) == n0
        self.tracker.record("decode", len(active), dt)
        for i in active:
            req = self.slot_req[i]
            req.out.append(int(nxt[i]))
            self.pos[i] += 1
            if (len(req.out) >= req.max_new or int(nxt[i]) == self.eos_id
                    or self.pos[i] >= self.max_len - 1):
                req.done = True
                self.slot_req[i] = None
        self.cycle_log.append({"admitted": admitted, "decoded": len(active),
                               "f": self.tracker.f()})
        return StepReport(admitted=admitted, decoded=len(active), dt=dt,
                          warm=warm)

    def _guard_limit(self) -> int:
        """Cycle budget proportional to outstanding work: every request
        needs ≲ 1 admission cycle plus max_new/quantum decode cycles; 8× is
        generous slack for admission backpressure and scheduler warm-up."""
        quantum = self.decode_quantum if self.fast else 1
        reqs = self.pending + [r for r in self.slot_req if r is not None]
        tokens = sum(max(1, r.max_new) for r in reqs)
        return 64 + 8 * (len(reqs) + -(-tokens // quantum))

    def run(self, requests: list[Request]) -> list[Request]:
        for r in requests:
            self.submit(r)
        guard, limit = 0, self._guard_limit()
        while self.pending or any(s is not None for s in self.slot_req):
            if guard >= limit:
                undone = sum(1 for r in requests if not r.done)
                raise EngineStallError(
                    f"no forward progress after {guard} cycles "
                    f"(limit {limit}): {len(self.pending)} pending, "
                    f"{undone} unfinished requests — engine scheduling bug "
                    f"or pool/slot starvation")
            self.step()
            guard += 1
        return requests


def make_engine(cfg: ModelConfig, ctx: ShardCtx, seed: int = 0,
                **kw) -> Engine:
    """Engine over seeded random weights, born on ``ctx``'s mesh in their
    final sharding by one jitted init (no eager per-leaf float32 copy)."""
    params = prm.materialize_sharded(model_defs(cfg),
                                     jax.random.PRNGKey(seed), ctx)
    return Engine(cfg, params, ctx, **kw)
