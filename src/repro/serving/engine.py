"""Continuous-batching serving engine with a fused, jit-compiled decode step
and a chunked-prefill / preemption scheduler (scheduler v2).

The engine owns:
  * a paged KV cache + block allocator (serving/cache.py),
  * dense per-slot SSM states (constant-size — SSM/hybrid archs need paged
    KV only for their attention layers), stored per period position with a
    leading ``n_periods`` axis so they scan with the layer stack,
  * a :class:`repro.serving.scheduler.Scheduler` that makes every policy
    decision: FIFO admission with lazy block allocation, chunked-prefill
    planning, and preemption of the youngest request under block pressure,
  * the jit-compiled decode and chunk-prefill steps over the running batch.

**Fused decode (default).** One ``jax.jit``-compiled function
``step(params, kv_state, ssm_states, tokens, lengths, table, active)``
advances every running sequence by one token: it scans the layer stack
(periods, like models/lm.py), computes attention with the *paged*
flash-decode kernel — K/V pages are read through the block table
(kernels/flash_decode.paged_flash_decode_partial), never materialized
densely — LSE-merges the fresh token's contribution analytically
(merge_partials), and appends all layers' new KV with ONE batched scatter
(cache.write_token_encoded) after the scan. Inactive batch slots route their
append to block id ``n_blocks`` (a dropped null write), so they can never
corrupt live pages. Block-table width is bucketed to powers of two, so the
jit cache holds at most one executable per (kind, T, table-bucket) triple
— kind is "decode" (T=1), "chunk" or "verify", all three running the same
paged multi-query attention read; ``trace_counts`` records every retrace
for the bounded-compile invariant.

**Prefill** comes in two schedules:

  * whole-prompt (``prefill_chunk=None``): admitted requests are grouped by
    context length and run through the model as one forward per group, then
    paged out with one all-layer scatter per sequence (the v1 behavior);
  * chunked (``prefill_chunk=N``): one jit-compiled chunk step pages N
    prompt tokens per engine step through the block table — the already-
    paged prefix is read with the *multi-query paged* kernel family
    (kernels/flash_decode.paged_flash_prefix_partial: every chunk row
    shares one page-tile fetch, no dense per-layer page view), the fresh
    chunk attends itself causally (causal_self_partial) and the partials
    LSE-merge — the same read algebra as fused decode and verify. SSM
    layers carry (conv, state) across chunks (blocks.ssm_apply
    T>1-with-cache), and the chunk's KV lands with one all-layer scatter
    whose padded tail routes to the null-write block. Decode for the
    running batch proceeds in the *same* engine step, so a long prompt no
    longer stalls every decoding request.

**Preemption.** Block tables grow lazily (scheduler.ensure_blocks); when the
pool runs dry the youngest active request is evicted and re-queued with its
generated prefix, then re-prefilled on re-admission (recompute preemption).
A victim's pages are scrubbed (cache.truncate_slots) before release, so a
preempted-then-resumed schedule leaves storage bit-identical to an
uncontended one. ``Engine.stats()`` surfaces the resulting latency
distributions: TTFT, TPOT and queue-time percentiles plus the preemption
count.

**Speculative decoding** (``speculate="ngram" | "draft:<config>"`` or any
proposer object; serving/speculate.py). Decode re-reads every weight per
token; speculation amortizes that read: a proposer guesses up to
``spec_depth`` continuation tokens per running request and ONE jit-compiled
*verify* step scores every request's window in a single multi-token forward
— the fused step's layer body with the attention read generalized to T
query rows (paged prefix partial + fresh-window causal partial, LSE-merged
via kernels/flash_decode.merge_partials). Proposals are accepted while they
equal the verify forward's own argmax, so greedy output is token-identical
to spec-off decode, and every row emits >= 1 token (the model's own bonus
token at the first disagreement). Rollback on rejection is exact: rejected
KV appends route to the null-write sentinel and SSM layers run a per-token
scan (blocks.ssm_apply_spec) emitting every intermediate (conv, state)
snapshot, from which the accepted prefix's state is selected. Per-request
speculation depth adapts to acceptance (Speculator back-off), and
``stats()`` reports accept_rate, proposed/accepted counters and the
verify-round depth histogram.

**Model-parallel sharding** (``mesh=``, a mesh with a ``model`` axis; see
launch/serve.py ``--model-parallel``). One serving ShardCtx
(parallel/sharding.make_serving_ctx) drives every placement: parameters
partition through the same ``state_shardings`` resolver training uses,
the paged KV pool splits its folded KV-head axis on head boundaries
(``kv_pool`` spec — each shard owns K/tp whole heads of every page, so
appends, truncation and the null-write sentinel stay shard-local), and
the SSM pools split their conv-channel / SSD-head axes. All three jit steps then compute *per-shard* paged
attention partials — head-sharded (o, m, l) merge shard-locally via
merge_partials, never a collective — and GSPMD materializes the
model-axis psum/all-gather at the row-parallel seams (wo, MLP down-proj,
SSM out_proj, logits), so each engine step is still ONE dispatch and
``trace_counts`` is degree-invariant. The scheduler, block tables and
allocator stay host-global: policy is device-count-agnostic, which is
what makes TP-vs-single-device scheduling (and therefore preemption
behavior) identical. Greedy output is token-identical to the unsharded
engine: sharded contractions accumulate in f32 (models/layers.dense) and
every value crossing a constraint boundary is computed at an explicit
precision (layers.swiglu, blocks._qkv/_ssm_pre/_expert_ffn,
cache.quant_encode), so TP differences are f32 reorder noise instead of
fusion-dependent bf16 rounding. The multi-host follow-up (a DCN axis
over this same seam) is in ROADMAP.

**Legacy decode** (``mode="legacy"``) keeps the paper-baseline per-layer
Python hot loop: per-layer eager dispatch, dense block gather, naive
attention. It exists as the measured baseline for benchmarks/bench_decode
and benchmarks/fig6_serving (--legacy), and as the parity oracle in tests.

**Failure semantics.** Every request ends in exactly one terminal state,
and every terminal transition releases the request's blocks through the
same scrub (``cache.truncate_slots``) → ``BlockAllocator.release`` path
preemption uses, so no exit can leak pages or leave stale KV bytes:

  * ``FINISHED`` — generation budget met (``Scheduler.finish``).
  * ``TIMED_OUT`` — the request's ``deadline_s`` (or the engine-wide
    ``default_deadline_s``) elapsed since arrival; a per-step sweep evicts
    it whether it is queued, prefilling or decoding.
  * ``CANCELLED`` — :meth:`Engine.cancel` revoked it; a request cancelled
    mid-speculative-window rolls back exactly (rejected appends were
    already null-writes, accepted ones are scrubbed with its pages).
  * ``REJECTED`` — ``submit`` refused it with a machine-readable reason
    (``empty_prompt`` / ``bad_max_new`` / ``unschedulable`` /
    ``queue_full``). The bounded waiting queue (``queue_cap``) makes
    overload shed load instead of queueing unboundedly; preemption
    re-queues bypass the cap.
  * ``FAILED`` — the step's in-jit non-finite-logit flag tripped for the
    request's row: it is quarantined (evicted, pages scrubbed, blocks
    freed) without disturbing the rest of the batch or adding a dispatch
    — the flag rides inside the same jitted step.

``Engine.run`` adds a no-progress watchdog: ``stall_limit`` consecutive
steps in which no request advances (no token, no prefill progress, no
admission, no terminal transition, no allocator movement) raise
:class:`StallError` naming the stuck requests instead of silently looping
to ``max_steps``. ``Engine.stats()`` reports per-cause terminal counts
(``finished`` / ``timed_out`` / ``cancelled`` / ``rejected`` /
``failed``). Deterministic fault injection — block squeezes, forced
allocator failures, delayed cancellation, NaN poisoning, deadline storms
— wires in through ``Engine(faults=FaultInjector(...))``
(serving/faults.py) behind a no-op default; the ``--chaos <seed>`` flag
of launch/serve.py drives it from the CLI.

The paper's serving benchmarks (Figs. 6-10) drive this engine with burst
arrivals and record per-request latency for CDFs plus aggregate throughput;
benchmarks/bench_latency.py adds Poisson arrivals and SLO percentiles.
"""
from __future__ import annotations

import math
import time
from collections import Counter
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.config import ArchConfig
# the one shared percentile definition (empty-window- and None-safe):
# stats() SLO percentiles and telemetry histogram snapshots must never
# disagree on edge cases (see core/stats.py; pinned by tests)
from repro.core.stats import percentile as _pct
from repro.models import blocks as B
from repro.models import layers as L
from repro.models.lm import LM
from repro.parallel.sharding import make_serving_ctx, state_shardings, \
    logical_by_path_of
from repro.serving import cache as C
from repro.serving.cache import PagedKVCache, PagedKVConfig
from repro.serving.prefix_cache import PrefixCache
from repro.serving.scheduler import (CANCELLED, FAILED, FINISHED, REJECTED,
                                     RUNNING, TERMINAL_STATES, TIMED_OUT,
                                     Rejected, Request, Scheduler)
from repro.serving.speculate import build_speculator
from repro.serving.telemetry import Telemetry
from repro.kernels import flash_decode as fd

__all__ = ["Engine", "Request", "Rejected", "StallError"]


class StallError(RuntimeError):
    """``Engine.run`` made no progress for ``stall_limit`` consecutive
    steps while work remained: a livelock (e.g. the pool never comes back
    from an injected squeeze, or an external co-user wedged the
    allocator). Raised instead of silently spinning to ``max_steps``;
    names every stuck request so the operator sees *who* is wedged."""

    def __init__(self, idle_steps: int, stuck: List[Request]):
        self.rids = [r.rid for r in stuck]
        names = ", ".join(
            f"rid={r.rid}({r.state}, prefilled={r.prefilled}, "
            f"out={len(r.output)})" for r in stuck)
        super().__init__(
            f"engine stalled: {idle_steps} consecutive steps without "
            f"progress; stuck requests: {names or '<none>'}")


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


class Engine:
    def __init__(self, cfg: ArchConfig, params, *, max_batch: int = 8,
                 n_blocks: int = 64, block_size: int = 16,
                 kv_quant: str = "none", greedy: bool = True,
                 mode: str = "fused", prefill_chunk: Optional[int] = None,
                 speculate=None, spec_depth: int = 4, mesh=None,
                 clock=time.monotonic, queue_cap: Optional[int] = None,
                 default_deadline_s: Optional[float] = None,
                 faults=None, stall_limit: int = 200,
                 prefix_cache: bool = False, telemetry=None):
        if mode not in ("fused", "legacy"):
            raise ValueError(f"mode must be 'fused' or 'legacy', got {mode!r}")
        if prefix_cache and mode != "fused":
            raise ValueError("prefix caching requires mode='fused' (suffix "
                             "prefill resumes through the chunked step)")
        if prefix_cache and prefill_chunk is None:
            raise ValueError(
                "prefix caching requires chunked prefill (prefill_chunk=N): "
                "a cache hit resumes the suffix through the chunk "
                "executable, and only a chunk-aligned resume reproduces "
                "the cache-off run's numerics bit-for-bit — the whole-"
                "prompt dense forward computes the same suffix with a "
                "different reduction order, which can flip greedy "
                "near-ties and break token parity")
        if stall_limit < 1:
            raise ValueError("stall_limit must be >= 1")
        self.spec = build_speculator(speculate, cfg, depth=spec_depth)
        if self.spec is not None and mode != "fused":
            raise ValueError("speculative decoding requires mode='fused' "
                             "(the verify step shares the fused layer body)")
        if mesh is not None and mode != "fused":
            raise ValueError("model-parallel serving requires mode='fused' "
                             "(the legacy per-layer loop stays the "
                             "single-device parity oracle)")
        if faults is not None and mode != "fused":
            raise ValueError("fault injection requires mode='fused' (the "
                             "NaN mask and finite flags ride the jitted "
                             "steps)")
        self.cfg = cfg
        # model-axis sharding: one ShardCtx drives every placement — params
        # through the training-side state_shardings resolver, activations
        # through the blocks' constrain() calls, the paged KV pool through
        # the "kv_pool" spec (folded KV-head axis split on head bounds). The
        # scheduler and block accounting stay host-global: policy is
        # device-count-agnostic.
        self.mesh = mesh
        self._ctx = make_serving_ctx(cfg, mesh) if mesh is not None else None
        self.tp_degree = int(mesh.shape["model"]) if mesh is not None else 1
        self.model = LM(cfg, ctx=self._ctx)
        if self._ctx is not None:
            logical = logical_by_path_of(self.model.param_specs())
            params = jax.device_put(
                params, state_shardings(self._ctx, params, logical,
                                        component="params"))
        self.params = params
        self.max_batch = max_batch
        self.block_size = block_size
        self.greedy = greedy
        self.mode = mode
        self.prefill_chunk = prefill_chunk
        self.clock = clock
        # attention layout: which period positions mix with attention, and
        # the (period, rank) -> flat attn-layer mapping used by the storage
        self._attn_pos = [i for i in range(self.model.period)
                          if self.model.kinds[i] == "attn"]
        self._ssm_pos = [i for i in range(self.model.period)
                         if self.model.kinds[i] == "ssm"]
        n_attn = len(self._attn_pos) * self.model.n_periods
        self.kv_cfg = PagedKVConfig(
            n_layers=max(n_attn, 1), n_kv_heads=max(cfg.n_kv_heads, 1),
            head_dim=max(cfg.head_dim, 1), n_blocks=n_blocks,
            block_size=block_size, kv_quant=kv_quant)
        kv_sharding = None
        if self._ctx is not None:
            pool_shape = (self.kv_cfg.n_layers, n_blocks, block_size,
                          self.kv_cfg.n_kv_heads * self.kv_cfg.head_dim)
            kv_sharding = NamedSharding(
                mesh, self._ctx.spec_for("kv_pool", pool_shape))
        self.kv = PagedKVCache(self.kv_cfg, sharding=kv_sharding)
        # cross-request prefix caching (serving/prefix_cache.py): full
        # prefill blocks are content-indexed in a radix trie; admission
        # shares the longest cached prefix at refcount+1 and prefill pages
        # only the novel suffix. For SSM/hybrid archs a match additionally
        # needs a recurrent-state snapshot, captured only at
        # chunk-schedule-aligned depths (``_ssm_snap_align``) so a resumed
        # suffix regroups the SSD scan exactly as a from-scratch prefill.
        self._prefix = None
        if prefix_cache:
            self._prefix = PrefixCache(block_size,
                                       track_ssm=bool(self._ssm_pos))
            self._prefix.scrub = self._scrub_block_ids
            # bitwise-parity alignment: a hit may only skip a prefix that
            # ends on a chunk boundary of the cache-off schedule — then
            # the resumed chunks partition [cached, len) exactly as a cold
            # prefill partitions them, so every attention reduction (and
            # SSD regrouping) runs in the same order. Skips at other
            # depths would move keys between the dense in-window and
            # paged read paths and perturb ulps.
            self._prefix.align_blocks = (
                prefill_chunk // math.gcd(prefill_chunk, block_size))
        self._ssm_snap_align = 1
        if self._ssm_pos:
            self._ssm_snap_align = (prefill_chunk if prefill_chunk
                                    else max(getattr(cfg, "ssm_chunk", 1), 1))
        self.sched = Scheduler(max_batch=max_batch, n_blocks=n_blocks,
                               block_size=block_size,
                               prefill_chunk=prefill_chunk,
                               queue_cap=queue_cap,
                               prefix_cache=self._prefix)
        self.finished: List[Request] = []
        # request-lifecycle hardening (PR 6): deadlines, load shedding,
        # fault injection, watchdog — see "Failure semantics" above
        self.default_deadline_s = default_deadline_s
        self.faults = faults
        self.stall_limit = stall_limit
        self.n_rejected = 0
        self.rejected_reasons: Counter = Counter()
        # sweep deadlines only when someone armed one: the hot path of a
        # deadline-free deployment stays untouched
        self._deadlines_armed = default_deadline_s is not None
        # (rid, layer period) scheduled for in-jit NaN poisoning during
        # the CURRENT step's forward; consumed by whichever jitted step
        # runs the rid's row, cleared at the end of the step
        self._nan_plan: Optional[tuple] = None
        self._ssm_states = self._init_ssm_states()
        # under a mesh the XLA read partitions on the (sharded) KV-head
        # axis of the pool out of the box; running the Pallas kernel
        # per-shard needs a shard_map wrapper — the multi-host ROADMAP item
        self._paged_impl = ("pallas"
                            if jax.default_backend() == "tpu" and mesh is None
                            else "xla")
        # one executable per (kind, T, table-bucket) triple — kinds are
        # "decode" (T=1), "chunk" and "verify"; trace_counts
        # observes every (re)trace of the jitted steps. KV/SSM state buffers
        # are donated: the caller always rebinds to the returned state, so
        # the cache is updated in place instead of copied every token
        # (backends without donation support fall back to a copy).
        self.trace_counts: Counter = Counter()
        self._fused_step = jax.jit(self._fused_step_impl,
                                   donate_argnums=(1, 2))
        self._chunk_step = jax.jit(self._chunk_step_impl,
                                   donate_argnums=(1, 2))
        self._verify_step = jax.jit(self._verify_step_impl,
                                    donate_argnums=(1, 2))
        # recompute-style preemption scrubs the victim's pages before the
        # allocator reuses them, so a preempted-then-resumed schedule leaves
        # the KV storage bit-identical to an uncontended one
        self.sched.on_preempt = self._scrub_preempted
        # whole-prompt prefill is jit-compiled too (one executable per
        # (group, length) shape): besides the speedup, compiled-vs-eager
        # bf16 fusion differences would otherwise make whole-prompt and
        # chunked prefill disagree on greedy tokens for SSD stacks
        self._prefill_fwd = jax.jit(self._prefill_fwd_impl)
        self.steps = 0
        self.prefill_tokens = 0
        self.decode_tokens = 0
        self.decode_time = 0.0
        self.prefill_time = 0.0
        # prefix-cache accounting: one lookup per admission, a hit when
        # any cached tokens were reused; cow counts defensive tail copies
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.prefix_tokens_reused = 0
        self.prefix_cow_copies = 0
        # observability (serving/telemetry.py): off by default. Every hook
        # is host-side and guarded on ``enabled`` — the jitted step
        # signatures carry no telemetry argument, so telemetry-on and
        # telemetry-off engines share executables, trace_counts and greedy
        # tokens bit-for-bit (pinned by tests/test_telemetry.py).
        # telemetry=True builds an enabled collector; a Telemetry instance
        # is adopted as-is (launchers pass one to pick fenced mode or to
        # export the trace after the run).
        if isinstance(telemetry, Telemetry):
            self.telemetry = telemetry
        else:
            self.telemetry = Telemetry(enabled=bool(telemetry))
        self.telemetry.bind(self)
        self.sched.tel = self.telemetry
        self.alloc.tel = self.telemetry
        if self._prefix is not None:
            self._prefix.tel = self.telemetry
        if self.spec is not None:
            self.spec.tel = self.telemetry

    # engine-level views over the scheduler's bookkeeping (the public
    # surface tests and benchmarks built against v1)
    @property
    def alloc(self):
        return self.sched.alloc

    @property
    def waiting(self):
        return self.sched.waiting

    @property
    def running(self):
        return self.sched.running

    # ------------------------------------------------------------------
    def _init_ssm_states(self):
        cfg, model = self.cfg, self.model
        states: Dict[str, Any] = {}
        base = None
        for pos in self._ssm_pos:
            if base is None:
                base = B.ssm_init_cache(cfg, self.max_batch)
            states[f"pos{pos}"] = jax.tree_util.tree_map(
                lambda x: jnp.zeros((model.n_periods,) + x.shape, x.dtype),
                base)
        sh = self._ssm_sharding_tree(states)
        if sh is not None:
            states = jax.device_put(states, sh)
        return states

    def _ssm_sharding_tree(self, states):
        """NamedSharding tree for the dense per-slot SSM pools: the model
        axis splits the same feature dims the ssm weights shard under TP —
        conv cache (n_periods, B, conv-1, channels) on its channel axis,
        SSD state (n_periods, B, heads, headdim, state) on its head axis
        (both the direct analogue of the KV pool's KV-head split; an
        indivisible dim degrades to replication for that leaf)."""
        if self._ctx is None or not states:
            return None
        mdl = self._ctx._mdl

        def place(path, a):
            name = getattr(path[-1], "key", None)
            entries = [None] * a.ndim
            if name == "conv":
                entries[-1] = mdl(a.shape[-1])
            elif name == "state":
                entries[2] = mdl(a.shape[2])
            return NamedSharding(self.mesh, P(*entries))

        return jax.tree_util.tree_map_with_path(place, states)

    def _constrain_state(self, kv_state, ssm_states):
        """Pin the post-step pools to their resident layout inside jit, so
        the donated buffers round-trip with stable shardings (no silent
        re-layout between steps under GSPMD)."""
        if self._ctx is None:
            return kv_state, ssm_states
        if kv_state:
            kv_state = jax.tree_util.tree_map(
                lambda a: jax.lax.with_sharding_constraint(
                    a, self.kv.sharding), kv_state)
        if ssm_states:
            ssm_states = jax.tree_util.tree_map(
                jax.lax.with_sharding_constraint, ssm_states,
                self._ssm_sharding_tree(ssm_states))
        return kv_state, ssm_states

    def _zero_ssm_slot(self, slot: int) -> None:
        """Reset one slot's SSM state (chunked prefill starts from zeros;
        whole-prompt prefill overwrites the slot with its snapshot instead)."""
        if not self._ssm_states:
            return
        self._ssm_states = jax.tree_util.tree_map(
            # repro: allow[CACHE-01] slot is a host int in [0, max_batch); no null-write sentinel on the slot axis
            lambda a: a.at[:, slot].set(0), self._ssm_states)

    def _restore_ssm_slot(self, req: Request) -> None:
        """Load the matched trie node's SSM snapshot into ``req``'s slot:
        the recurrent-state half of a prefix-cache hit (KV blocks cover
        the attention half). The snapshot was captured after exactly
        ``cached_tokens`` tokens at a chunk-schedule-aligned boundary, so
        the resumed suffix prefill regroups the SSD scan identically to a
        from-scratch prefill."""
        node = req.cache_node
        if node is None or node.ssm is None:
            self._zero_ssm_slot(req.slot)
            return
        self._ssm_states = jax.tree_util.tree_map(
            # repro: allow[CACHE-01] req.slot is a host int the scheduler just assigned; no sentinel on the slot axis
            lambda full, snap: full.at[:, req.slot].set(snap),
            self._ssm_states, node.ssm)

    # ------------------------------------------------------------------
    # Prefix-cache plumbing: registration as prefill pages blocks out,
    # scrub-on-reclaim, and the defensive copy-on-write tail guard
    # ------------------------------------------------------------------

    def _snapshot_ssm_slot(self, slot: int):
        return jax.tree_util.tree_map(lambda a: a[:, slot],
                                      self._ssm_states)

    def _cache_register(self, req: Request) -> None:
        """Index every newly-FULL block of ``req``'s paged context in the
        radix trie. Resumes below ``req.cache_node`` (the deepest node
        already on its chain — matched at admission or registered by an
        earlier chunk), so each block registers once. For SSM archs a
        snapshot of the slot state attaches to the deepest node only when
        the paged length sits on a chunk-schedule-aligned block boundary
        (``_ssm_snap_align``) — a borrower resuming there regroups its
        remaining chunks / SSD scan exactly as a cold prefill would."""
        pc = self._prefix
        if pc is None:
            return
        bs = self.block_size
        paged = req.prefilled
        n_full = paged // bs
        node = req.cache_node
        depth = node.depth if node is not None else 0
        if n_full <= depth:
            return
        ctx = req.context_tokens()
        snap = None
        if self._ssm_pos and paged == n_full * bs \
                and paged % self._ssm_snap_align == 0:
            snap = self._snapshot_ssm_slot(req.slot)
        for j in range(depth, n_full):
            edge = tuple(int(t) for t in ctx[j * bs:(j + 1) * bs])
            node = pc.register(node, edge, req.blocks[j],
                               ssm=snap if j == n_full - 1 else None)
        req.cache_node = node

    def _scrub_block_ids(self, ids: List[int]) -> None:
        """Zero whole blocks (scrub-on-reclaim hook for the prefix
        cache's second-chance pool)."""
        if self._attn_pos and ids:
            self.kv.state = C.scrub_blocks(self.kv.state, ids)

    def _cow_tail(self, req: Request, pos: Optional[int] = None) -> None:
        """Copy-on-write guard before a write at token position ``pos``
        (default: the next decode append): if the block it lands in is
        shared (refcount > 1) or cache-registered, copy it into a private
        block first. Structurally this cannot trigger — only FULL prefill
        blocks are ever indexed/shared, writes always resume past the
        shared prefix in a block with free tail slots — but the guard
        makes the write path safe by construction rather than by
        argument, and the chaos/property suites exercise it directly."""
        if self._prefix is None or not req.blocks:
            return
        if pos is None:
            pos = req.length - 1
        bidx = pos // self.block_size
        if bidx >= len(req.blocks):
            return                  # ensure_blocks will grow a fresh one
        b = req.blocks[bidx]
        if self.alloc.refcount[b] == 1 and not self._prefix.is_cached(b):
            return
        [fresh] = self.alloc.alloc(1)
        if self._attn_pos:
            self.kv.state = C.copy_block(self.kv.state, b, fresh)
        req.blocks[bidx] = fresh
        self.alloc.release([b])
        self.prefix_cow_copies += 1

    # ------------------------------------------------------------------
    # Scheduling entry points (policy lives in serving/scheduler.py)
    # ------------------------------------------------------------------

    def submit(self, req: Request) -> None:
        """Enqueue a request, or raise :class:`Rejected` (validation or
        load shedding — see scheduler.submit). Rejections are counted
        per reason in ``stats()`` before re-raising."""
        req.arrival = req.arrival or self.clock()
        if req.deadline_s is None:
            req.deadline_s = self.default_deadline_s
        if req.deadline_s is not None:
            self._deadlines_armed = True
        try:
            self.sched.submit(req)
        except Rejected as e:
            self.n_rejected += 1
            self.rejected_reasons[e.reason] += 1
            req.finish_time = req.finish_time or self.clock()
            self.telemetry.req_reject(req, e.reason)
            raise
        self.telemetry.req_submit(req)

    # ------------------------------------------------------------------
    # Request lifecycle: cancellation, deadlines, quarantine, injection
    # ------------------------------------------------------------------

    def live_requests(self) -> List[Request]:
        """Every request still in the schedule (waiting or active)."""
        return list(self.sched.waiting) + [r for r in self.sched.running
                                           if r is not None]

    def cancel(self, rid: int) -> bool:
        """Revoke a request wherever it is — queued, prefilling, decoding
        or mid-speculative-window. An active request leaves through the
        scrub→release eviction path (its pages are zeroed before the
        allocator reuses them, so a cancelled speculation window rolls
        back exactly); a waiting one just leaves the queue. Returns False
        when ``rid`` is not in the schedule (already terminal/unknown)."""
        for r in self.live_requests():
            if r.rid == rid:
                self._evict_terminal(r, CANCELLED)
                return True
        return False

    def arm_nan(self, rid: int, period: int) -> None:
        """Schedule in-jit NaN poisoning of ``rid``'s hidden state at
        layer-period ``period`` for the current step (fault injection)."""
        if not 0 <= period < self.model.n_periods:
            raise ValueError(f"period {period} outside "
                             f"[0, {self.model.n_periods})")
        self._nan_plan = (rid, period)

    def arm_deadlines(self) -> None:
        """Enable the per-step deadline sweep (used after deadlines are
        stamped onto already-submitted requests, e.g. a deadline storm)."""
        self._deadlines_armed = True

    def _evict_terminal(self, req: Request, state: str) -> None:
        """Move ``req`` to a terminal state through the preempt→scrub→
        release path and account it with the finished cohort."""
        if self.spec is not None and req.state == RUNNING:
            self.spec.abandon(req)
        self.sched.evict_terminal(req, state, self.clock())
        self.finished.append(req)

    def _sweep_deadlines(self, now: float) -> None:
        for r in self.live_requests():
            if r.deadline_s is not None and now - r.arrival >= r.deadline_s:
                self._evict_terminal(r, TIMED_OUT)

    def _inj_mask(self, bsz: int, rows) -> np.ndarray:
        """(n_periods, bsz) NaN-injection mask for a step; ``rows`` yields
        (batch-row index, request). All-False in normal operation — the
        mask is a traced argument of every jitted step, so arming it never
        retraces or adds a dispatch, and faulted/fault-free engines share
        executables (their surviving rows stay bitwise-identical)."""
        inj = np.zeros((self.model.n_periods, bsz), bool)
        if self._nan_plan is not None:
            rid, period = self._nan_plan
            for b, r in rows:
                if r.rid == rid:
                    inj[period, b] = True
        return inj

    # ------------------------------------------------------------------
    # Whole-prompt prefill: one forward per group of equal-length contexts;
    # page out attention KV with one all-layer scatter per sequence;
    # snapshot SSM states into the slots. Resume-aware: a preempted request
    # re-prefills its prompt *plus generated prefix* and keeps decoding.
    # ------------------------------------------------------------------

    def _prefill(self, reqs: List[Request]) -> None:
        # prefix-cache hits (prefilled > 0) cannot reach this path: the
        # cache requires chunked prefill, where _prefill_chunk_tick
        # resumes at req.prefilled natively
        by_len: Dict[int, List[Request]] = {}
        for r in reqs:
            by_len.setdefault(r.context_len(), []).append(r)
        with self.telemetry.phase("prefill", rows=len(reqs)):
            for t in sorted(by_len):
                self._prefill_group(by_len[t], t)

    def _prefill_fwd_impl(self, params, toks):
        logits, cache, _ = self.model.prefill(params, {"tokens": toks})
        return logits, cache

    def _prefill_group(self, group: List[Request], t: int) -> None:
        tel = self.telemetry
        tel.mark_kind("prefill")
        with tel.phase("inputs"):
            toks = jnp.asarray([r.context_tokens() for r in group], jnp.int32)
        # the forward, then the page-out of its KV and SSM states
        with tel.phase("launch"):
            logits, cache = self._prefill_fwd(self.params, toks)
            if self._attn_pos:
                ks, vs = [], []
                for pos in self._attn_pos:
                    c = cache[f"pos{pos}"]
                    if isinstance(c, dict) and "self" in c:
                        c = c["self"]
                    ks.append(c["k"])            # (n_periods, G, T, K, hd)
                    vs.append(c["v"])
                lkv = (len(group), t, self.kv_cfg.n_kv_heads,
                       self.kv_cfg.head_dim)
                # (L, G, T, ..)
                k_all = jnp.stack(ks, axis=1).reshape((-1,) + lkv)
                v_all = jnp.stack(vs, axis=1).reshape((-1,) + lkv)
            for g, r in enumerate(group):
                if self._attn_pos:
                    self.kv.write_prefill((k_all[:, g], v_all[:, g]),
                                          r.blocks)
                for pos in self._ssm_pos:
                    c = cache[f"pos{pos}"]
                    st = self._ssm_states[f"pos{pos}"]
                    self._ssm_states[f"pos{pos}"] = jax.tree_util.tree_map(
                        # repro: allow[CACHE-01] r.slot is a host int the scheduler just assigned; no sentinel on the slot axis
                        lambda full, new: full.at[:, r.slot].set(new[:, g]),
                        st, c)
        with tel.phase("wait"):
            next_tok = np.asarray(jnp.argmax(logits, axis=-1))
            row_ok = np.asarray(jnp.all(
                jnp.isfinite(logits.astype(jnp.float32)), axis=-1))
        with tel.phase("finish"):
            now = self.clock()
            for g, r in enumerate(group):
                if not row_ok[g]:       # poisoned prompt forward: quarantine
                    self._evict_terminal(r, FAILED)
                    continue
                if not r.output:    # fresh request: this IS the first token
                    r.output.append(int(next_tok[g]))
                    r.first_token_time = now
                    tel.req_first_token(r)
                # resumed request: the recomputed token is already output[-1]
                r.prefilled = t
                r.state = RUNNING
                tel.req_running(r)
                self.prefill_tokens += t
                self._cache_register(r)

    # ------------------------------------------------------------------
    # Shared layer body. The fused decode step, the chunked-prefill step
    # and the speculative verify step scan the SAME body over the layer
    # stack; each caller parameterizes only
    #   * the attention read path (``attn_read``): paged multi-query
    #     prefix partial + fresh-window causal partial + LSE merge for
    #     all three (fused decode is the T=1 window). The read reaches the
    #     paged pool in place: the step closes over the whole stacked
    #     (L, n_blocks, block, K*hd) pool as a loop invariant, and the body
    #     hands ``attn_read`` the flat attention-layer index
    #     ``period * attn_per_period + r``, which the paged read takes as
    #     its layer coordinate. No step slices the pool per layer or moves
    #     it; the post-scan scatter writes the donated pool in place, and
    #   * the SSM cache plumbing (``ssm_step``): T=1 decode with an
    #     active-slot mask, T>1 chunk-continue, or the per-token verify
    #     scan that emits every intermediate state for exact rollback.
    # Everything else — the encode-as-stored KV contract (attend to the
    # fresh tokens exactly as the cache will store them, reuse the encoded
    # form for the post-scan page-out), the scan ys collection, and the
    # moe/ffn dispatch — is written once here. Divergence used to be
    # caught only by the parity tests; now it cannot happen.
    # ------------------------------------------------------------------

    def _make_stack_body(self, *, positions, attn_read, ssm_step):
        cfg, model, ctx = self.cfg, self.model, self._ctx
        quant = self.kv_cfg.kv_quant
        n_attn_pp = len(self._attn_pos)

        def body(x, xs):
            lp, period, ssm_slice, inj = xs
            # fault injection: poison selected rows' hidden state entering
            # this layer period with NaN (inj is (B,) bool, all-False in
            # normal operation — a traced select, never a retrace). The
            # poisoned row's logits turn non-finite, tripping the step's
            # quarantine flag; other rows are untouched (row-independent).
            x = jnp.where(inj[:, None, None],
                          jnp.asarray(jnp.nan, x.dtype), x)
            new_kv: Dict[str, list] = {}
            new_ssm: Dict[str, Any] = {}
            r = 0
            for pos in range(model.period):
                pp = lp[f"pos{pos}"]
                if model.kinds[pos] == "attn":
                    h = L.rmsnorm(x, pp["mix"]["ln"], cfg.norm_eps)
                    q, k, v = B._qkv(h, pp["mix"], cfg, ctx,
                                     positions=positions)   # (B, T, H, hd)
                    # pin q/k/v to their rounded bits: the quant encode,
                    # the attention read and the post-scan scatter must
                    # all consume the SAME values in every compilation.
                    # Without the barrier, XLA's excess-precision pass may
                    # elide the bf16 rounding for one consumer and not
                    # another depending on fusion shape — which differs
                    # between eager (legacy), jit (fused) and TP-sharded
                    # executables, silently breaking token parity.
                    q, k, v = jax.lax.optimization_barrier((q, k, v))
                    kq, ks = C.quant_encode(k, quant)
                    vq, vs = C.quant_encode(v, quant)
                    out = attn_read(q, (kq, ks, vq, vs),
                                    period * n_attn_pp + r)
                    # head-sharded attention produces shard-complete heads
                    # (partials LSE-merge locally); the row-parallel wo
                    # contraction is where the model-axis psum materializes
                    out = B._constrain(ctx, out, "act_q")
                    y = L.dense(out, pp["mix"]["wo"], n_in=2)
                    x = x + B._constrain(ctx, y, "hidden")
                    new_kv.setdefault("k", []).append(kq)
                    new_kv.setdefault("v", []).append(vq)
                    if ks is not None:
                        new_kv.setdefault("k_scale", []).append(ks)
                        new_kv.setdefault("v_scale", []).append(vs)
                    r += 1
                else:
                    x, nc = ssm_step(x, pp["mix"], ssm_slice[f"pos{pos}"])
                    new_ssm[f"pos{pos}"] = nc
                if model.fkinds[pos] == "moe":
                    x, _ = B.moe_apply(x, pp["ffn"], cfg, ctx,
                                       capacity_mult=4.0)
                else:
                    x = B.ffn_apply(x, pp["ffn"], cfg, ctx)
            kv_ys = {kk: jnp.stack(vv) for kk, vv in new_kv.items()}
            return x, (kv_ys, new_ssm)

        return body

    def _scan_stack(self, body, params, x, ssm_xs, inj):
        """Run ``body`` over the layer stack; each iteration gets its
        period's weights, its period index (for the layer coordinate of
        the paged reads), its SSM slices and its injection row."""
        periods = jnp.arange(self.model.n_periods, dtype=jnp.int32)
        return jax.lax.scan(body, x, (params["blocks"], periods, ssm_xs, inj))

    def _collect_enc(self, kv_ys):
        """Scan ys (n_periods, R, B, T, ...) -> storage-ready
        (L, B*T, ...) for one all-layer write_token_encoded scatter."""
        n_l = self.model.n_periods * len(self._attn_pos)
        return {kk: vv.reshape((n_l, -1) + vv.shape[4:])
                for kk, vv in kv_ys.items()}

    # ------------------------------------------------------------------
    # Chunked prefill: one jit-compiled step pages `prefill_chunk` context
    # tokens of ONE sequence through its block table. The already-paged
    # prefix [0, ctx) is read THROUGH the table with the multi-query
    # paged partial (all chunk rows share each page-tile fetch — no dense
    # per-layer page view); the fresh chunk attends itself causally and
    # the partials LSE-merge, the same read algebra as fused decode and
    # verify. SSM layers carry (conv, state) across chunks. Ragged tails
    # are right-padded to the chunk size so the jit cache stays one
    # executable per (chunk, table-bucket): padded KV routes to the
    # null-write block and padded SSM positions are dt-masked
    # (state-neutral); padded attention rows compute garbage that nothing
    # reads (the next token comes from row n_valid - 1).
    # ------------------------------------------------------------------

    def _chunk_step_impl(self, params, kv_state, ssm_states, tokens, ctx,
                         n_valid, table, slot, inj):
        cn = int(tokens.shape[1])
        mbb = int(table.shape[1])
        # runs only when jit (re)traces: bounded-compile accounting
        self.trace_counts[("chunk", cn, mbb)] += 1
        cfg, model = self.cfg, self.model
        bs = self.block_size
        n_attn_pp = len(self._attn_pos)
        sm_scale = 1.0 / float(np.sqrt(max(cfg.head_dim, 1)))

        x = model._embed_in(params, tokens)                  # (1, C, d)
        positions = ctx + jnp.arange(cn, dtype=jnp.int32)[None, :]
        ssm_xs = jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=1),
            ssm_states)
        table0 = table[0]

        def attn_read(q, enc, layer):
            kq, ks, vq, vs = enc
            o_c, m_c, l_c = fd.paged_flash_prefix_partial(
                q, kv_state["k"], kv_state["v"], layer, table, ctx[None],
                k_scale=kv_state.get("k_scale"),
                v_scale=kv_state.get("v_scale"),
                impl=self._paged_impl, sm_scale=sm_scale)
            # attend to the fresh chunk as the cache will store it (int8
            # roundtrip under kv_quant), causal within the chunk
            ka = C.quant_decode(kq, ks, jnp.float32)
            va = C.quant_decode(vq, vs, jnp.float32)
            o_n, m_n, l_n = fd.causal_self_partial(q, ka, va,
                                                   sm_scale=sm_scale)
            out = fd.merge_partials([(o_c, m_c, l_c), (o_n, m_n, l_n)])
            return out.astype(q.dtype)

        def ssm_step(x, pp_mix, st):
            return B.ssm_apply(x, pp_mix, cfg, self._ctx, cache=st,
                               n_valid=n_valid)

        body = self._make_stack_body(positions=positions,
                                     attn_read=attn_read, ssm_step=ssm_step)
        x, (kv_ys, new_ssm) = self._scan_stack(body, params, x, ssm_xs, inj)

        last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
        logits = model._head(params, last)[:, 0]
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)[0]
        # non-finite-logit quarantine flag: computed in-jit so a poisoned
        # request costs no extra dispatch; the host evicts it as FAILED
        ok = jnp.all(jnp.isfinite(logits.astype(jnp.float32)))

        if n_attn_pp:
            enc = self._collect_enc(kv_ys)
            tok_pos = ctx + jnp.arange(cn, dtype=jnp.int32)
            valid = jnp.arange(cn) < n_valid
            blk, off = C.append_slots(
                jnp.broadcast_to(table0[None], (cn, mbb)), tok_pos, bs,
                self.kv_cfg.n_blocks, valid)
            kv_state = C.write_token_encoded(kv_state, enc, blk, off)
        if self._ssm_pos:
            ssm_states = jax.tree_util.tree_map(
                lambda full, new: jax.lax.dynamic_update_slice_in_dim(
                    full, new, slot, axis=1),
                ssm_states, new_ssm)
        kv_state, ssm_states = self._constrain_state(kv_state, ssm_states)
        return kv_state, ssm_states, next_token, ok

    def _prefill_chunk_tick(self) -> None:
        plan = self.sched.next_prefill_chunk()
        if plan is None:
            return
        req, start, n = plan
        if not self.sched.ensure_blocks(req, start + n):
            return      # only elders hold blocks: wait for them to finish
        tel = self.telemetry
        tel.mark_kind("chunk")
        tc0 = tel.clock() if tel.enabled else 0.0
        with tel.phase("chunk", rid=req.rid, start=start, n=n):
            with tel.phase("inputs"):
                self._cow_tail(req, pos=start)
                seq = req.context_tokens()
                cn = self.prefill_chunk
                chunk = seq[start:start + n] + [0] * (cn - n)
                # fixed table width per request footprint: every chunk of
                # this request compiles against the same bucket
                mbb = _next_pow2(self.sched._blocks_for(len(seq)))
                table = np.zeros((1, mbb), np.int32)
                table[0, : len(req.blocks)] = req.blocks
                args = (jnp.asarray([chunk], jnp.int32),
                        jnp.asarray(start, jnp.int32),
                        jnp.asarray(n, jnp.int32), jnp.asarray(table),
                        jnp.asarray(req.slot, jnp.int32),
                        jnp.asarray(self._inj_mask(1, [(0, req)])))
            with tel.phase("launch"):
                kv_state, ssm_states, next_tok, ok = self._chunk_step(
                    self.params, self.kv.state, self._ssm_states, *args)
            self.kv.state = kv_state
            if self._ssm_pos:
                self._ssm_states = ssm_states
            with tel.phase("wait"):
                ok = bool(ok)
            with tel.phase("finish"):
                if not ok:
                    # poisoned mid-prefill: quarantine before any state
                    # leaks into the request's lifecycle (its pages are
                    # scrubbed on eviction)
                    self._evict_terminal(req, FAILED)
                    return
                req.prefilled = start + n
                self.prefill_tokens += n
                tel.req_chunk(req, tc0, start, n)
                self._cache_register(req)
                if req.prefilled >= len(seq):
                    if not req.output:  # fresh request: its first token
                        req.output.append(int(next_tok))
                        req.first_token_time = self.clock()
                        tel.req_first_token(req)
                    req.state = RUNNING
                    tel.req_running(req)

    # ------------------------------------------------------------------
    # Fused decode: the whole step — embed, layer-stack scan with paged
    # flash attention, head, greedy sample, batched KV append — is ONE
    # jit-compiled function of pytrees. Host work per step is O(max_batch).
    # ------------------------------------------------------------------

    def _fused_step_impl(self, params, kv_state, ssm_states, tokens,
                         lengths, table, active, inj):
        # runs only when jit (re)traces: bounded-compile accounting.
        # Keys are uniform (kind, T, table-bucket) across the three step
        # kinds; fused decode is the T=1 member of the read family (batch
        # is pinned to max_batch, so it never varies a key).
        self.trace_counts[("decode", 1, int(table.shape[1]))] += 1
        cfg, model = self.cfg, self.model
        bs = self.block_size
        n_attn_pp = len(self._attn_pos)
        sm_scale = 1.0 / float(np.sqrt(max(cfg.head_dim, 1)))

        x = model._embed_in(params, tokens[:, None])
        positions = lengths[:, None]
        ssm_xs = ssm_states

        def attn_read(q, enc, layer):
            kq, ks, vq, vs = enc
            o_c, m_c, l_c = fd.paged_flash_decode_partial(
                q[:, 0], kv_state["k"], kv_state["v"], layer, table,
                lengths, k_scale=kv_state.get("k_scale"),
                v_scale=kv_state.get("v_scale"),
                impl=self._paged_impl, sm_scale=sm_scale)
            # the fresh token attends to itself via a single-position
            # causal partial, LSE-merged with the cache — its KV lands in
            # the pages AFTER the scan, in one batched all-layer scatter.
            # Attend to the token as the cache will store it (int8
            # roundtrip under kv_quant), so this step and every later one
            # see the same values; the encoded form doubles as the scan
            # output so the post-scan scatter never re-quantizes.
            ka = C.quant_decode(kq, ks, jnp.float32)
            va = C.quant_decode(vq, vs, jnp.float32)
            o_n, m_n, l_n = fd.causal_self_partial(q, ka, va,
                                                   sm_scale=sm_scale)
            out = fd.merge_partials(
                [(o_c[:, None], m_c[:, None], l_c[:, None]),
                 (o_n, m_n, l_n)])
            return out.astype(q.dtype)

        def ssm_step(x, pp_mix, st):
            x, nc = B.ssm_apply(x, pp_mix, cfg, self._ctx, cache=st)
            # inactive slots keep their state: a slot mid-way through
            # chunked prefill must not have its carried (conv, ssd) state
            # advanced by the running batch's decode steps (the SSM
            # analogue of the null-write block for inactive KV appends)
            nc = jax.tree_util.tree_map(
                lambda new, old: jnp.where(
                    active.reshape((-1,) + (1,) * (new.ndim - 1)),
                    new, old),
                nc, st)
            return x, nc

        body = self._make_stack_body(positions=positions,
                                     attn_read=attn_read, ssm_step=ssm_step)
        x, (kv_ys, new_ssm) = self._scan_stack(body, params, x, ssm_xs, inj)

        logits = model._head(params, x)[:, 0]
        next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # per-row non-finite-logit quarantine flags, computed in-jit so a
        # poisoned request adds no dispatch; the host only consults the
        # flags of live rows (inactive rows may legitimately be garbage)
        row_ok = jnp.all(jnp.isfinite(logits.astype(jnp.float32)), axis=-1)

        if n_attn_pp:
            enc = self._collect_enc(kv_ys)
            # inactive slots -> block id n_blocks: a dropped null write
            blk, off = C.append_slots(table, lengths, bs,
                                      self.kv_cfg.n_blocks, active)
            kv_state = C.write_token_encoded(kv_state, enc, blk, off)
        new_lengths = jnp.where(active, lengths + 1, lengths)
        kv_state, new_ssm = self._constrain_state(kv_state, new_ssm)
        return kv_state, new_ssm, next_tokens, new_lengths, row_ok

    def _decode_fused(self, live: List[Request]) -> None:
        if not live:
            return
        tel = self.telemetry
        tel.mark_kind("decode")
        with tel.phase("decode", rows=len(live),
                       kv_tokens=sum(r.length - 1 for r in live)):
            with tel.phase("inputs"):
                bsz = self.max_batch
                tokens = np.zeros((bsz,), np.int32)
                lengths = np.zeros((bsz,), np.int32)
                active = np.zeros((bsz,), bool)
                for r in live:
                    self._cow_tail(r)
                mbb = _next_pow2(max(len(r.blocks) for r in live))
                table = np.zeros((bsz, mbb), np.int32)
                for r in live:
                    tokens[r.slot] = r.output[-1]
                    lengths[r.slot] = r.length - 1      # current KV length
                    active[r.slot] = True
                    table[r.slot, : len(r.blocks)] = r.blocks
                args = (jnp.asarray(tokens), jnp.asarray(lengths),
                        jnp.asarray(table), jnp.asarray(active),
                        jnp.asarray(self._inj_mask(
                            bsz, ((r.slot, r) for r in live))))
            with tel.phase("launch"):
                kv_state, ssm_states, next_tokens, _, row_ok = \
                    self._fused_step(self.params, self.kv.state,
                                     self._ssm_states, *args)
            self.kv.state = kv_state
            if ssm_states:
                self._ssm_states = ssm_states
            with tel.phase("wait"):
                next_tokens = np.asarray(next_tokens)
                row_ok = np.asarray(row_ok)
            with tel.phase("finish"):
                self._finish_step(live, next_tokens, row_ok=row_ok)

    # ------------------------------------------------------------------
    # Speculative decoding: a proposer (serving/speculate.py) guesses up
    # to K continuation tokens per running request and ONE jit-compiled
    # verify forward scores every request's whole window — the multi-
    # token generalization of the fused decode step over the shared
    # layer body (paged prefix partial + fresh-window causal partial,
    # LSE-merged via kernels/flash_decode.merge_partials). A row with no
    # proposals runs the window at depth 0, which IS a fused decode row,
    # so spec mode keeps one device dispatch per engine step.
    # Proposals are accepted while they equal the verify forward's own
    # argmax, so greedy output is token-identical to non-speculative
    # decode; the first disagreement contributes the model's own (bonus)
    # token, so every row emits >= 1 token per step. Exact rollback on
    # partial acceptance: rejected KV appends route to the null-write
    # sentinel (they are never stored), and SSM layers run the per-token
    # verify scan (blocks.ssm_apply_spec) that emits every intermediate
    # (conv, state) snapshot, so the state after the accepted prefix is
    # selected — never recomputed, never contaminated by rejections.
    # ------------------------------------------------------------------

    def _verify_step_impl(self, params, kv_state, ssm_states, tokens, ctx,
                          n_valid, table, active, inj):
        cn = int(tokens.shape[1])        # 1 + spec depth (padded, fixed)
        mbb = int(table.shape[1])
        # runs only when jit (re)traces: bounded-compile accounting
        self.trace_counts[("verify", cn, mbb)] += 1
        cfg, model = self.cfg, self.model
        bs = self.block_size
        n_attn_pp = len(self._attn_pos)
        bsz = tokens.shape[0]
        sm_scale = 1.0 / float(np.sqrt(max(cfg.head_dim, 1)))

        x = model._embed_in(params, tokens)                  # (B, T, d)
        positions = ctx[:, None] + jnp.arange(cn, dtype=jnp.int32)[None, :]
        ssm_xs = ssm_states
        # per-row validity: [last token, proposals...] then padding; an
        # inactive slot has n_valid == 0 (whole row inert)
        valid_rows = jnp.arange(cn)[None, :] < n_valid[:, None]

        def attn_read(q, enc, layer):
            kq, ks, vq, vs = enc
            o_c, m_c, l_c = fd.paged_flash_prefix_partial(
                q, kv_state["k"], kv_state["v"], layer, table, ctx,
                k_scale=kv_state.get("k_scale"),
                v_scale=kv_state.get("v_scale"),
                impl=self._paged_impl, sm_scale=sm_scale)
            ka = C.quant_decode(kq, ks, jnp.float32)
            va = C.quant_decode(vq, vs, jnp.float32)
            o_n, m_n, l_n = fd.causal_self_partial(q, ka, va,
                                                   sm_scale=sm_scale)
            out = fd.merge_partials([(o_c, m_c, l_c), (o_n, m_n, l_n)])
            return out.astype(q.dtype)

        def ssm_step(x, pp_mix, st):
            return B.ssm_apply_spec(x, pp_mix, cfg, self._ctx, cache=st,
                                    valid=valid_rows)

        body = self._make_stack_body(positions=positions,
                                     attn_read=attn_read, ssm_step=ssm_step)
        x, (kv_ys, new_ssm) = self._scan_stack(body, params, x, ssm_xs, inj)

        logits = model._head(params, x)                      # (B, T, V)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # per-row quarantine flags over the VALID window positions only
        # (padded positions compute garbage nothing reads)
        fin = jnp.isfinite(logits.astype(jnp.float32))
        row_ok = jnp.all(jnp.logical_or(fin, ~valid_rows[:, :, None]),
                         axis=(1, 2))
        # acceptance: the proposals are the input tokens shifted left;
        # count the leading run where proposal == the model's own argmax
        match = jnp.logical_and(
            tokens[:, 1:] == greedy[:, :-1],
            jnp.arange(cn - 1)[None, :] < (n_valid - 1)[:, None])
        n_acc = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1),
                        axis=1)                              # (B,)

        if n_attn_pp:
            enc = self._collect_enc(kv_ys)          # rows: (B, T) C-order
            tok_pos = (ctx[:, None]
                       + jnp.arange(cn, dtype=jnp.int32)[None, :])
            # rejected proposals and inactive slots -> the null-write
            # sentinel: their KV is never stored, so no post-hoc
            # truncation is needed
            accepted = jnp.logical_and(
                jnp.arange(cn)[None, :] <= n_acc[:, None],
                active[:, None])
            blk, off = C.append_slots(
                jnp.repeat(table, cn, axis=0), tok_pos.reshape(-1), bs,
                self.kv_cfg.n_blocks, accepted.reshape(-1))
            kv_state = C.write_token_encoded(kv_state, enc, blk, off)
        if self._ssm_pos:
            # new_ssm leaves are (n_periods, T, B, ...): the state after
            # every token of the window; roll each row back to its
            # accepted prefix by selecting index n_acc[b] (the state
            # after inputs 0..n_acc). Inactive rows never advanced, so
            # any index returns their carried state unchanged.
            def sel(st):
                idx = n_acc.reshape((1, 1, bsz) + (1,) * (st.ndim - 3))
                idx = jnp.broadcast_to(idx, (st.shape[0], 1) + st.shape[2:])
                return jnp.take_along_axis(st, idx, axis=1)[:, 0]

            ssm_states = jax.tree_util.tree_map(sel, new_ssm)
        kv_state, ssm_states = self._constrain_state(kv_state, ssm_states)
        return kv_state, ssm_states, greedy, n_acc, row_ok

    def _decode_spec(self, live: List[Request]) -> None:
        """One batched verify round over every live request: gather
        proposals, grow block tables for the speculative appends, run the
        verify step, emit accepted+bonus tokens. A request the proposer
        is silent on (or whose speculative growth would require evicting
        an elder) rides along at depth 0 — plain decode semantics."""
        if not live:
            return
        tel = self.telemetry
        with tel.phase("verify", rows=len(live)):
            with tel.phase("inputs"):
                bsz = self.max_batch
                t = self.spec.depth + 1
                tokens = np.zeros((bsz, t), np.int32)
                ctx = np.zeros((bsz,), np.int32)
                n_valid = np.zeros((bsz,), np.int32)
                active = np.zeros((bsz,), bool)
                n_props: Dict[int, int] = {}
                rows: List[Request] = []
                for r in sorted(live, key=lambda r: (r.arrival, r.rid)):
                    if r.state != RUNNING:  # preempted by an elder's growth
                        continue
                    budget = r.max_new_tokens - len(r.output) - 1
                    k = self.spec.depth_for(r, budget) if budget >= 1 else 0
                    props = self.spec.propose(r, k) if k >= 1 else []
                    # the verify window appends up to len(props)+1 tokens
                    # of KV; iteration is oldest-first, so growth can only
                    # preempt rows not yet gathered (strictly younger
                    # requests)
                    if props and not self.sched.ensure_blocks(
                            r, r.length + len(props)):
                        props = []
                    tokens[r.slot, 0] = r.output[-1]
                    tokens[r.slot, 1: 1 + len(props)] = props
                    ctx[r.slot] = r.length - 1          # current KV length
                    n_valid[r.slot] = 1 + len(props)
                    active[r.slot] = True
                    n_props[r.rid] = len(props)
                    rows.append(r)
                if not rows:
                    return
                tel.mark_kind("verify")
                for r in rows:
                    self._cow_tail(r)
                mbb = _next_pow2(max(len(r.blocks) for r in rows))
                table = np.zeros((bsz, mbb), np.int32)
                for r in rows:
                    table[r.slot, : len(r.blocks)] = r.blocks
                # window width bucketed to powers of two, capped at
                # depth+1: when back-off shrinks every row's proposals, the
                # step pays for a narrow executable instead of the
                # full-depth window. Bounded compile: one executable per
                # (window-bucket, table-bucket) pair.
                t = min(_next_pow2(int(np.max(n_valid))),
                        self.spec.depth + 1)
                args = (jnp.asarray(tokens[:, :t]), jnp.asarray(ctx),
                        jnp.asarray(n_valid), jnp.asarray(table),
                        jnp.asarray(active),
                        jnp.asarray(self._inj_mask(
                            bsz, ((r.slot, r) for r in rows))))
            with tel.phase("launch"):
                kv_state, ssm_states, greedy, n_acc, row_ok = \
                    self._verify_step(self.params, self.kv.state,
                                      self._ssm_states, *args)
            self.kv.state = kv_state
            if self._ssm_pos:
                self._ssm_states = ssm_states
            with tel.phase("wait"):
                greedy = np.asarray(greedy)
                n_acc = np.asarray(n_acc)
                row_ok = np.asarray(row_ok)
            with tel.phase("finish"):
                now = self.clock()
                for r in rows:
                    if not row_ok[r.slot]:
                        # quarantine: nothing the poisoned forward produced
                        # is emitted or recorded; eviction scrubs its pages
                        # (the appended window KV included) before the
                        # blocks free
                        self._evict_terminal(r, FAILED)
                        continue
                    j = int(n_acc[r.slot])
                    emitted = [int(tok) for tok in greedy[r.slot, : j + 1]]
                    r.output.extend(emitted)
                    self.decode_tokens += len(emitted)
                    if n_props[r.rid]:
                        self.spec.record(r, proposed=n_props[r.rid],
                                         accepted=j)
                    if len(r.output) >= r.max_new_tokens:
                        self.sched.finish(r, now)
                        self.finished.append(r)

    def _scrub_preempted(self, victim: Request) -> None:
        """Zero a preemption victim's pages before the allocator reuses
        them (cache.truncate_slots): partial overwrites by the next owner
        then can't leave stale bytes, so a preempted-then-resumed schedule
        keeps the storage bit-identical to an uncontended one.

        With the prefix cache on, only the victim's PRIVATE blocks are
        scrubbed: a shared block (refcount > 1) stays live for its other
        owners, and a cache-registered block keeps its bytes in the
        second-chance pool — it is scrubbed on reclaim instead, which is
        what makes the victim's own re-admission a cheap cache hit."""
        if not (self._attn_pos and victim.blocks):
            return
        if self._prefix is None:
            self.kv.truncate_slots(victim.blocks, 0)
            return
        rc = self.alloc.refcount
        private = [b for b in victim.blocks
                   if rc[b] == 1 and not self._prefix.is_cached(b)]
        if private:
            self.kv.state = C.scrub_blocks(self.kv.state, private)

    def warmup(self, max_seq_len: int,
               prompt_lens: Optional[List[int]] = None) -> None:
        """Pre-compile the jitted steps for the table bucket implied by
        ``max_seq_len`` (prompt + generation budget), the way a serving
        deployment compiles before taking traffic. No state is mutated.

        ``prompt_lens`` (optional): the distinct prompt lengths of the
        expected trace. Chunked prefill compiles one chunk executable per
        *request-footprint* table bucket (``_prefill_chunk_tick`` pins the
        table width to the request's own context bucket, not the global
        max), so a mixed-length trace demands one executable per distinct
        bucket — warming only the max length would leave every shorter
        bucket to compile on the serving path. When ``prompt_lens`` is
        given, every pow2 bucket between the smallest prompt bucket and
        the max footprint is warmed, not just the buckets the prompts
        themselves imply: a preemption victim re-prefills prompt PLUS
        generated prefix, which lands in intermediate buckets no fresh
        prompt uses."""
        mbb = _next_pow2(-(-max_seq_len // self.block_size))
        bsz = self.max_batch
        # the steps donate their state args: hand them throwaway copies so
        # the live cache buffers survive the discarded warmup calls. Each
        # call's output (a whole pool) is dropped as soon as it is ready:
        # held beside the next call's copy it would make three pools, more
        # than a device whose pool is fitted to hold two
        if self.mode == "fused" and self.spec is None:
            jax.block_until_ready(self._fused_step(
                self.params,
                jax.tree_util.tree_map(jnp.copy, self.kv.state),
                jax.tree_util.tree_map(jnp.copy, self._ssm_states),
                jnp.zeros((bsz,), jnp.int32), jnp.zeros((bsz,), jnp.int32),
                jnp.zeros((bsz, mbb), jnp.int32), jnp.zeros((bsz,), bool),
                jnp.zeros((self.model.n_periods, bsz), bool)))
        if self.prefill_chunk is not None:
            cn = self.prefill_chunk
            buckets = {mbb}
            if prompt_lens:
                lo = min(_next_pow2(self.sched._blocks_for(t))
                         for t in prompt_lens)
                b = lo
                while b <= mbb:     # cover re-prefill (victim) footprints
                    buckets.add(b)
                    b *= 2
            for cb in sorted(buckets):
                jax.block_until_ready(self._chunk_step(
                    self.params,
                    jax.tree_util.tree_map(jnp.copy, self.kv.state),
                    jax.tree_util.tree_map(jnp.copy, self._ssm_states),
                    jnp.zeros((1, cn), jnp.int32),
                    jnp.asarray(0, jnp.int32), jnp.asarray(cn, jnp.int32),
                    jnp.zeros((1, cb), jnp.int32), jnp.asarray(0, jnp.int32),
                    jnp.zeros((self.model.n_periods, 1), bool)))
        if self.spec is not None:
            # build every (window-bucket, table-bucket) executable the
            # depth policy can demand: pow2 window widths capped at
            # depth+1 (adaptive back-off narrows the verify window)
            widths = sorted({min(_next_pow2(k), self.spec.depth + 1)
                             for k in range(1, self.spec.depth + 2)})
            for t in widths:
                jax.block_until_ready(self._verify_step(
                    self.params,
                    jax.tree_util.tree_map(jnp.copy, self.kv.state),
                    jax.tree_util.tree_map(jnp.copy, self._ssm_states),
                    jnp.zeros((bsz, t), jnp.int32),
                    jnp.zeros((bsz,), jnp.int32),
                    jnp.zeros((bsz,), jnp.int32),
                    jnp.zeros((bsz, mbb), jnp.int32),
                    jnp.zeros((bsz,), bool),
                    jnp.zeros((self.model.n_periods, bsz), bool)))

    # ------------------------------------------------------------------
    # Legacy decode: the paper-baseline per-layer Python hot loop (eager
    # dispatch per layer, dense block gather, naive attention). Kept as
    # the measured baseline and parity oracle for the fused path.
    # ------------------------------------------------------------------

    def _decode_batch(self, live: List[Request]) -> None:
        if not live:
            return
        tel = self.telemetry
        tel.mark_kind("decode")
        with tel.phase("decode", rows=len(live),
                       kv_tokens=sum(r.length - 1 for r in live)):
            with tel.phase("inputs"):
                bsz = self.max_batch
                tokens = np.zeros((bsz, 1), np.int32)
                lengths = np.zeros((bsz,), np.int32)
                active = np.zeros((bsz,), bool)
                max_blocks = max(len(r.blocks) for r in live)
                table = np.zeros((bsz, max_blocks), np.int32)
                for r in live:
                    tokens[r.slot, 0] = r.output[-1]
                    lengths[r.slot] = r.length - 1      # current KV length
                    active[r.slot] = True
                    table[r.slot, : len(r.blocks)] = r.blocks
                args = (jnp.asarray(tokens), jnp.asarray(lengths),
                        jnp.asarray(table), jnp.asarray(active))
            with tel.phase("launch"):
                logits = self._legacy_forward(*args)
            with tel.phase("wait"):
                next_tokens = np.asarray(jnp.argmax(logits, axis=-1))
                # legacy path quarantines on the host (no injection mask
                # here; the flag still catches organically-poisoned
                # weights/state)
                row_ok = np.asarray(jnp.all(
                    jnp.isfinite(logits.astype(jnp.float32)), axis=-1))
            with tel.phase("finish"):
                self._finish_step(live, next_tokens, row_ok=row_ok)

    def _legacy_forward(self, tokens, lengths, table, active):
        """The legacy decode forward, one eager dispatch per layer; returns
        the (max_batch, vocab) logits."""
        cfg = self.cfg
        x = jnp.take(self.params["embed"], tokens, axis=0)
        attn_layer = 0
        for i, kind in enumerate(cfg.layer_kinds()):
            pos, per = i % self.model.period, i // self.model.period
            pp = jax.tree_util.tree_map(
                lambda a: a[per], self.params["blocks"][f"pos{pos}"])
            if kind == "attn":
                x = self._paged_attn(x, pp["mix"], attn_layer, table,
                                     lengths, active)
                attn_layer += 1
            else:
                full = self._ssm_states[f"pos{pos}"]
                st = jax.tree_util.tree_map(lambda a: a[per], full)
                x, nc = B.ssm_apply(x, pp["mix"], cfg, None, cache=st)
                # inactive slots keep their state (see fused step)
                nc = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(
                        active.reshape((-1,) + (1,) * (new.ndim - 1)),
                        new, old),
                    nc, st)
                self._ssm_states[f"pos{pos}"] = jax.tree_util.tree_map(
                    # repro: allow[CACHE-01] per is the host-side period loop index; inactive slots were select-masked above
                    lambda a, n: a.at[per].set(n), full, nc)
            if self.model.fkinds[pos] == "moe":
                x, _ = B.moe_apply(x, pp["ffn"], cfg, None, capacity_mult=4.0)
            else:
                x = B.ffn_apply(x, pp["ffn"], cfg, None)
        x = L.rmsnorm(x, self.params["final_ln"], cfg.norm_eps)
        if cfg.tie_embeddings:
            w = self.params["embed"].T
        else:
            w = self.params["head"]
        return L.dense(x, w)[:, 0]

    def _paged_attn(self, x, p, attn_layer: int, table, lengths, active):
        cfg = self.cfg
        h = L.rmsnorm(x, p["ln"], cfg.norm_eps)
        q, k, v = B._qkv(h, p, cfg, None, positions=lengths[:, None])
        # append the new token to its page; inactive slots (all-zero table
        # rows) become null writes instead of corrupting block 0
        quant = self.kv_cfg.kv_quant
        blk, off = C.append_slots(table, lengths, self.block_size,
                                  self.kv_cfg.n_blocks, active)
        kq, ks = C.quant_encode(k[:, 0], quant)
        vq, vs = C.quant_encode(v[:, 0], quant)
        enc = {"k": kq, "v": vq}
        if ks is not None:
            enc["k_scale"], enc["v_scale"] = ks, vs
        st = dict(self.kv.state)
        for key, val in enc.items():
            # (B, K, hd | 1) -> one folded pool row per batch slot
            st[key] = st[key].at[attn_layer, blk, off].set(
                val.reshape(val.shape[0], -1).astype(st[key].dtype),
                mode="drop")
        self.kv.state = st
        # f32 softmax accumulation: matches the flash-decode kernels' and
        # the fused step's numerics (bf16 p·v rounding would make the two
        # paths' greedy tokens drift apart)
        kd, vd = self.kv.gather(attn_layer, table, dtype=jnp.float32)
        out = L.attention(q.astype(jnp.float32), kd, vd, mode="naive",
                          causal=False, kv_len=lengths + 1).astype(q.dtype)
        y = L.dense(out, p["wo"], n_in=2)
        return x + y

    # ------------------------------------------------------------------

    def _finish_step(self, live: List[Request], next_tokens,
                     row_ok=None) -> None:
        now = self.clock()
        for r in live:
            if row_ok is not None and not row_ok[r.slot]:
                # non-finite logits: quarantine the row (evict as FAILED,
                # scrub pages, free blocks) without emitting its token
                self._evict_terminal(r, FAILED)
                continue
            r.output.append(int(next_tokens[r.slot]))
            self.decode_tokens += 1
            if len(r.output) >= r.max_new_tokens:
                self.sched.finish(r, now)
                self.finished.append(r)

    def step(self) -> None:
        # telemetry wraps each segment below without reordering it: every
        # hook is host-side, so a telemetry-off step executes exactly the
        # code it always did, plus profiler spans that are inert unless a
        # profiler session is recording
        tel = self.telemetry
        with tel.phase("step", step=self.steps):
            tel.step_begin(self.steps)
            # fault injection + deadline sweep run before admission so a
            # stormed/cancelled request never occupies a slot this step
            with tel.phase("sweep"):
                if self.faults is not None:
                    self.faults.on_step_begin(self)
                if self._deadlines_armed:
                    self._sweep_deadlines(self.clock())
            with tel.phase("schedule"):
                admitted = self.sched.admit(self.clock())
                for r in admitted:
                    if self._prefix is not None:
                        self.prefix_lookups += 1
                        if r.cached_tokens:
                            self.prefix_hits += 1
                            self.prefix_tokens_reused += r.cached_tokens
                    # a cache hit resumes the recurrent state from the matched
                    # node's snapshot; everything else starts the slot from zero
                    if r.cached_tokens and self._ssm_pos:
                        self._restore_ssm_slot(r)
                    elif self.prefill_chunk is not None:
                        self._zero_ssm_slot(r.slot)
            t0 = self.clock()
            with tel.phase("dispatch"):
                if self.prefill_chunk is None:
                    if admitted:
                        self._prefill(admitted)
                else:
                    self._prefill_chunk_tick()
            self.prefill_time += self.clock() - t0
            # grow each decoding request's block table for this step's
            # append; under pressure this preempts strictly-younger
            # request(s) — so re-check states after the loop — and a request
            # that could only grow by evicting an elder sits this step out
            with tel.phase("schedule"):
                deferred = set()
                for r in self.sched.decode_candidates():
                    if r.state == RUNNING and \
                            not self.sched.ensure_blocks(r, r.length):
                        deferred.add(r.rid)
                live = [r for r in self.sched.running
                        if r is not None and r.state == RUNNING
                        and r.rid not in deferred]
            t0 = self.clock()
            with tel.phase("dispatch"):
                if self.mode != "fused":
                    self._decode_batch(live)
                elif self.spec is not None:
                    self._decode_spec(live)
                else:
                    self._decode_fused(live)
            self.decode_time += self.clock() - t0
            # fenced mode: attribute async device time to the step that
            # dispatched it (paper-style module-wise timing at smoke scale —
            # serializes the dispatch pipeline, so never on by default)
            if tel.enabled and tel.fenced:
                with tel.phase("sync"):
                    jax.block_until_ready((self.kv.state, self._ssm_states))
            # a NaN plan is good for exactly one step's forward, armed or not
            self._nan_plan = None
            self.steps += 1
            tel.step_end(self)

    def _progress_key(self):
        """Snapshot of everything that changes when any request advances:
        a token emitted, prefill progress, admission, preemption, any
        terminal transition, or allocator movement. Two equal consecutive
        keys mean the step did nothing for anyone."""
        return (len(self.finished), self.sched.n_preemptions,
                len(self.sched.waiting), self.alloc.n_free,
                self.alloc.n_reclaimable,
                tuple((r.rid, r.state, r.prefilled, len(r.output))
                      for r in self.sched.running if r is not None))

    def run(self, max_steps: int = 10_000) -> List[Request]:
        """Drive steps until the schedule drains (or ``max_steps``). A
        no-progress watchdog raises :class:`StallError` after
        ``stall_limit`` consecutive idle steps instead of silently
        spinning — e.g. when an injected squeeze never returns the pool."""
        idle = 0
        key = self._progress_key()
        while self.sched.has_work and self.steps < max_steps:
            self.step()
            new_key = self._progress_key()
            if new_key == key:
                idle += 1
                if idle >= self.stall_limit:
                    raise StallError(idle, self.live_requests())
            else:
                idle, key = 0, new_key
        return self.finished

    def reset_stats(self) -> None:
        """Clear request history and counters while keeping compiled steps
        and cache storage — benchmarks run a warmup trace, reset, then
        measure the same engine with every executable already built.
        Requires a quiescent engine (no waiting/running requests)."""
        if self.sched.has_work:
            raise RuntimeError("reset_stats() on an engine with live work")
        self.finished = []
        self.steps = 0
        self.prefill_tokens = 0
        self.decode_tokens = 0
        self.decode_time = 0.0
        self.prefill_time = 0.0
        self.sched.n_preemptions = 0
        self.n_rejected = 0
        self.rejected_reasons = Counter()
        # prefix-cache counters reset; the cache CONTENTS survive — a
        # benchmark's measured pass runs against the warmed cache, which
        # is the steady state a deployment sees
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.prefix_tokens_reused = 0
        self.prefix_cow_copies = 0
        if self.spec is not None:
            self.spec.reset()
        # collected telemetry resets with the stats (the trace epoch and
        # any compiled executables survive, like the cache contents do)
        self.telemetry.reset()

    def snapshot_base(self) -> Dict[str, Any]:
        """Structured engine aggregates: the engine-owned sections of the
        schema-v1 metrics snapshot (see docs/observability.md).
        ``telemetry.snapshot()`` merges these with the registry/timeline
        sections; the legacy flat :meth:`stats` dict is a mechanical
        flattening of exactly these values — one computation, two views.
        Safe on an idle or just-reset engine (every window guards empty).
        """
        done = self.finished
        lat = [r.finish_time - r.arrival for r in done if r.finish_time]
        ttft = [t for t in (r.ttft() for r in done) if t is not None]
        tpot = [t for t in (r.tpot() for r in done) if t is not None]
        queue = [t for t in (r.queue_time() for r in done) if t is not None]
        # explicit empty-window guard: must be safe right after
        # reset_stats() and mid-burst (no finished request yet). The old
        # one-line ternary was already short-circuit-safe (the condition
        # evaluates before max()/min()), but only by operator-precedence
        # subtlety — a refactor hazard. This spells the guard out and a
        # regression test pins the zeroed-throughput behavior.
        if done:
            wall = (max((r.finish_time or 0.0) for r in done)
                    - min(r.arrival for r in done))
        else:
            wall = 0.0
        toks = sum(len(r.output) for r in done)
        pct = _pct
        # per-cause terminal accounting: every request that ever entered
        # the schedule shows up in exactly one of these buckets (rejected
        # ones never entered, so they count from the submit-side counter)
        causes = Counter(r.state for r in done)
        occ = self.alloc.occupancy()
        return {
            "engine": {
                "steps": self.steps,
                "mode": self.mode,
                "prefill_chunk": self.prefill_chunk or 0,
                "model_parallel": self.tp_degree,
            },
            "requests": {
                "completed": len(done),
                "finished": causes.get(FINISHED, 0),
                "timed_out": causes.get(TIMED_OUT, 0),
                "cancelled": causes.get(CANCELLED, 0),
                "failed": causes.get(FAILED, 0),
                "rejected": self.n_rejected,
                "rejected_reasons": dict(self.rejected_reasons),
            },
            "latency": {
                "e2e": {"mean": float(np.mean(lat)) if lat else 0.0,
                        "p50": pct(lat, 50), "p99": pct(lat, 99)},
                "ttft": {"mean": float(np.mean(ttft)) if ttft else 0.0,
                         "p50": pct(ttft, 50), "p95": pct(ttft, 95),
                         "p99": pct(ttft, 99)},
                "tpot": {"mean": float(np.mean(tpot)) if tpot else 0.0,
                         "p50": pct(tpot, 50), "p95": pct(tpot, 95),
                         "p99": pct(tpot, 99)},
                "queue": {"mean": float(np.mean(queue)) if queue else 0.0},
            },
            "throughput": {
                "tok_s": toks / wall if wall > 0 else 0.0,
                "decode_tok_s": (self.decode_tokens / self.decode_time
                                 if self.decode_time > 0 else 0.0),
                "decode_tokens": self.decode_tokens,
                "prefill_tokens": self.prefill_tokens,
                "decode_time_s": self.decode_time,
                "prefill_time_s": self.prefill_time,
            },
            # pool pressure is 1 - available/total: a cached-but-
            # reclaimable block is capacity (one alloc away from free),
            # not pressure — the occupancy split itemizes it
            "pool": {
                "utilization": self.alloc.utilization(),
                "owned": occ["owned"],
                "cached_reclaimable": occ["cached_reclaimable"],
                "free": occ["free"],
            },
            # prefix-cache effectiveness: hit rate over admissions (0.0
            # when the cache is off or nothing was admitted — safe right
            # after reset_stats()), resident index size, and total
            # prefill tokens skipped via cached blocks
            "prefix_cache": {
                "hit_rate": (self.prefix_hits / self.prefix_lookups
                             if self.prefix_lookups else 0.0),
                "cached_blocks": (self._prefix.n_cached_blocks
                                  if self._prefix is not None else 0),
                "tokens_reused": self.prefix_tokens_reused,
                "cow_copies": self.prefix_cow_copies,
            },
            "scheduler": {
                "preemptions": self.sched.n_preemptions,
                "queue_depth": len(self.sched.waiting),
            },
            "spec": self.spec.stats() if self.spec is not None else {},
        }

    def snapshot(self) -> Dict[str, Any]:
        """The stable machine-readable snapshot (schema v1): engine
        aggregates + telemetry registry/timeline. Works with telemetry
        disabled (those sections are simply empty)."""
        return self.telemetry.snapshot()

    def stats(self) -> Dict[str, float]:
        """Legacy flat stats dict — now a thin compatibility view: every
        key is a mechanical flattening of :meth:`snapshot_base`, so the
        two surfaces can never disagree. Prefer :meth:`snapshot` (stable
        schema, structured sections) in new code."""
        s = self.snapshot_base()
        req, lat, thr = s["requests"], s["latency"], s["throughput"]
        pool, pc = s["pool"], s["prefix_cache"]
        return {
            **s["spec"],
            "requests": req["completed"],
            "finished": req["finished"],
            "timed_out": req["timed_out"],
            "cancelled": req["cancelled"],
            "failed": req["failed"],
            "rejected": req["rejected"],
            "rejected_reasons": req["rejected_reasons"],
            "model_parallel": s["engine"]["model_parallel"],
            "throughput_tok_s": thr["tok_s"],
            "mean_latency_s": lat["e2e"]["mean"],
            "p50_latency_s": lat["e2e"]["p50"],
            "p99_latency_s": lat["e2e"]["p99"],
            "mean_ttft_s": lat["ttft"]["mean"],
            "p50_ttft_s": lat["ttft"]["p50"],
            "p95_ttft_s": lat["ttft"]["p95"],
            "p99_ttft_s": lat["ttft"]["p99"],
            "mean_tpot_s": lat["tpot"]["mean"],
            "p50_tpot_s": lat["tpot"]["p50"],
            "p95_tpot_s": lat["tpot"]["p95"],
            "p99_tpot_s": lat["tpot"]["p99"],
            "mean_queue_s": lat["queue"]["mean"],
            "preemptions": s["scheduler"]["preemptions"],
            "kv_utilization": pool["utilization"],
            "kv_blocks_owned": pool["owned"],
            "kv_blocks_cached_reclaimable": pool["cached_reclaimable"],
            "kv_blocks_free": pool["free"],
            "prefix_cache_hit_rate": pc["hit_rate"],
            "cached_blocks": pc["cached_blocks"],
            "cached_tokens_reused": pc["tokens_reused"],
            "prefix_cow_copies": pc["cow_copies"],
            "decode_tokens": thr["decode_tokens"],
            "prefill_tokens": thr["prefill_tokens"],
            "decode_time_s": thr["decode_time_s"],
            "prefill_time_s": thr["prefill_time_s"],
            "decode_tok_s": thr["decode_tok_s"],
        }
