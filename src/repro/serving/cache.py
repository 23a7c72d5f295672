"""Paged KV cache with a token-granular block allocator.

The TPU-native analogue of vLLM's PagedAttention / LightLLM's TokenAttention
(paper §II-D): HBM is carved into fixed blocks of `block_size` tokens; a
sequence owns a *block table* (list of block ids) instead of a contiguous
span, so fragmentation is bounded by one block per sequence and arbitrary
prefix sharing is possible.

The storage layer is split in two:

  * **pure functions** (`quant_encode` / `quant_decode` / `write_prefill` /
    `write_token` / `gather`) that operate on a plain *state pytree*
    ``{"k", "v", "k_scale", "v_scale"}`` — these are what the jit-compiled
    fused decode step (serving/engine.py) traces through;
  * the :class:`PagedKVCache` convenience wrapper that owns a state pytree
    and mutates it in place for the host-driven legacy path and tests.

Every configuration gets one storage layout, (L, n_blocks, block, K*hd)
per k/v: every attention layer's pages stacked on axis 0, and a token's kv
heads side by side on the last (lane) axis, so a page row is lane-dense
for any head size. The paged reads address it in place by (layer, block)
(kernels/flash_decode.py) and the steps scatter into it in place, so no
step copies or slices the pool; the per-head (..., K, hd) view is a
reshape taken only where a reader needs heads apart (:func:`gather`, the
XLA read, tests).

Int8KV (LightLLM) is supported by storing quantized KV + per-(block,
position, head) float32 scales, (L, n_blocks, block, K), doubling token
capacity. Scatters use ``mode="drop"`` so an out-of-range block id acts as
a *null write* — the engine routes inactive batch slots to block id
``n_blocks`` to mask their appends.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class PagedKVConfig:
    n_layers: int
    n_kv_heads: int
    head_dim: int
    n_blocks: int            # total HBM blocks
    block_size: int = 256    # tokens per block (128-aligned for the MXU)
    kv_quant: str = "none"   # none | int8


class OutOfBlocks(RuntimeError):
    """Raised by :meth:`BlockAllocator.alloc` when the free list is short.

    Callers that want admission control should check :attr:`n_free` first;
    the scheduler additionally treats a raise from ``alloc`` itself as
    *backpressure* (requeue / wait a step) rather than a crash, so an
    allocator that runs dry mid-step — a racing co-user, or the
    fault-injection hook :meth:`BlockAllocator.fail_next` — degrades the
    schedule instead of taking the engine down (serving/faults.py drives
    exactly this path in the chaos suite).
    """


class BlockAllocator:
    """Ref-counted free-list allocator over KV blocks (host-side).

    Contract: ``alloc(n)`` either returns exactly ``n`` block ids (each at
    refcount 1) or raises :class:`OutOfBlocks` — it never returns ``None``
    or a partial list. ``release`` *decrements*: a block only leaves a
    table's ownership when its count drops to zero, which is what lets
    several requests reference the same physical prefix block
    (serving/prefix_cache.py). ``release`` still enforces the owned/free
    invariant per call: every id must be a real block currently referenced
    by the caller. A double-release used to silently append the id to the
    free list twice, after which two requests could be handed the same
    block and corrupt each other's KV; now it raises ``ValueError`` at the
    offending call.

    With a prefix cache attached (:meth:`attach_cache`):

      * ``release`` routes a refcount-zero *cached* block into the cache's
        LRU second-chance pool instead of the free list — bytes stay valid
        for a future prefix match, and nothing is scrubbed on release;
      * ``alloc`` reclaims from that pool (scrub-on-reclaim, LRU-first)
        when the free list alone is short;
      * :meth:`share` takes an extra reference on an already-resident
        block, reviving it from the second-chance pool if needed.

    ``n_available`` (free + cached-reclaimable) is the admission-control
    quantity; ``n_free`` remains the strict free-list length.

    :meth:`fail_next` is the deterministic fault-injection hook: the next
    N calls to ``alloc`` raise :class:`OutOfBlocks` regardless of the free
    list, without mutating it — the chaos harness (serving/faults.py) uses
    it to prove the scheduler survives an allocator that runs dry mid-step.
    """

    def __init__(self, n_blocks: int):
        self.free: List[int] = list(range(n_blocks - 1, -1, -1))
        self._free_set = set(self.free)
        self.n_blocks = n_blocks
        self.refcount: List[int] = [0] * n_blocks
        self.cache = None           # optional PrefixCache
        self._fail_next = 0
        # optional Telemetry (serving/telemetry.py), wired by the engine:
        # block-movement counters for the metrics registry, nothing else
        self.tel = None

    def attach_cache(self, cache) -> None:
        """Install a :class:`~repro.serving.prefix_cache.PrefixCache` as
        the second-chance pool / reclaim source."""
        self.cache = cache

    def fail_next(self, n: int = 1) -> None:
        """Arm ``n`` injected failures: each of the next ``n`` ``alloc``
        calls raises :class:`OutOfBlocks` and leaves the free list intact."""
        if n < 0:
            raise ValueError("fail_next needs n >= 0")
        self._fail_next += n

    def alloc(self, n: int) -> List[int]:
        if self._fail_next > 0:
            self._fail_next -= 1
            raise OutOfBlocks(
                f"injected allocator failure (requested {n} blocks, "
                f"{len(self.free)} nominally free)")
        if len(self.free) < n and self.cache is not None:
            reclaimed = self.cache.reclaim(n - len(self.free))
            self.free.extend(reclaimed)
            self._free_set.update(reclaimed)
            if reclaimed and self.tel is not None and self.tel.enabled:
                self.tel.registry.count("blocks_reclaimed", len(reclaimed))
        if len(self.free) < n:
            raise OutOfBlocks(
                f"requested {n} blocks, only {len(self.free)} free")
        out = [self.free.pop() for _ in range(n)]
        self._free_set.difference_update(out)
        for b in out:
            self.refcount[b] = 1
        if n and self.tel is not None and self.tel.enabled:
            self.tel.registry.count("blocks_allocated", n)
        return out

    def share(self, blocks: List[int]) -> None:
        """Take one extra reference on each block (prefix reuse). Blocks
        must be resident: either referenced by some table (refcount > 0)
        or parked in the prefix cache's second-chance pool, from which
        they are revived. Sharing a free block would alias live pages —
        that raises, same contract as a bad release."""
        for b in blocks:
            if b < 0 or b >= self.n_blocks:
                raise ValueError(f"share of block {b} outside the pool "
                                 f"[0, {self.n_blocks})")
            if b in self._free_set:
                raise ValueError(
                    f"share of block {b}: it is on the free list — its "
                    f"bytes are not a valid cached prefix")
        for b in blocks:
            if self.refcount[b] > 0:
                self.refcount[b] += 1
            else:
                if self.cache is None or not self.cache.revive(b):
                    raise ValueError(
                        f"share of block {b}: refcount is zero and it is "
                        f"not parked in the prefix cache")
                self.refcount[b] = 1
        if blocks and self.tel is not None and self.tel.enabled:
            self.tel.registry.count("blocks_shared", len(blocks))

    def release(self, blocks: List[int]) -> None:
        seen = set()
        for b in blocks:
            if b < 0 or b >= self.n_blocks:
                raise ValueError(f"release of block {b} outside the pool "
                                 f"[0, {self.n_blocks})")
            if b in self._free_set or b in seen or self.refcount[b] == 0:
                raise ValueError(
                    f"double release of block {b}: it is already on the "
                    f"free list (freed blocks may have been reallocated — "
                    f"this would hand one page to two owners)")
            seen.add(b)
        freed = []
        for b in blocks:
            self.refcount[b] -= 1
            if self.refcount[b] == 0:
                if self.cache is not None and self.cache.is_cached(b):
                    self.cache.on_unreferenced(b)
                else:
                    freed.append(b)
        self.free.extend(freed)
        self._free_set.update(freed)
        if freed and self.tel is not None and self.tel.enabled:
            self.tel.registry.count("blocks_freed", len(freed))

    @property
    def n_free(self) -> int:
        return len(self.free)

    @property
    def n_reclaimable(self) -> int:
        """Cached blocks at refcount zero — evictable on demand."""
        return self.cache.n_unreferenced if self.cache is not None else 0

    @property
    def n_available(self) -> int:
        """Blocks obtainable by one ``alloc``: free + cached-reclaimable.
        This is what admission control and growth should gate on — a
        parked cached block is capacity, not pressure."""
        return len(self.free) + self.n_reclaimable

    def occupancy(self) -> Dict[str, int]:
        """Pool split: {owned (referenced), cached_reclaimable, free}."""
        free = len(self.free)
        cached = self.n_reclaimable
        return {"owned": self.n_blocks - free - cached,
                "cached_reclaimable": cached, "free": free}

    def utilization(self) -> float:
        return 1.0 - self.n_available / max(self.n_blocks, 1)


# ==========================================================================
# Pure functional storage ops (jit-safe; used by the fused decode step)
# ==========================================================================


def quant_encode(x: jax.Array, kv_quant: str
                 ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Encode activations for storage: identity, or int8 + per-vector scale.

    The scale multiplies by the f32 constant 1/127 instead of dividing by
    127: XLA rewrites division-by-constant into reciprocal-multiplication
    in some compilations and not others (fusion-context dependent), and a
    one-f32-ulp scale difference between the eager legacy path and the
    jitted fused step shifts dequantized attention reads enough to split
    their greedy tokens. Stating the multiply makes every compilation —
    eager, jit, TP-sharded — produce the same scale bits."""
    if kv_quant != "int8":
        return x, None
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-6) * np.float32(1.0 / 127.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                 -127, 127).astype(jnp.int8)
    return q, scale


def quant_decode(q: jax.Array, scale: Optional[jax.Array],
                 dtype=jnp.bfloat16) -> jax.Array:
    if scale is None:
        return q.astype(dtype)
    return (q.astype(jnp.float32) * scale).astype(dtype)


def init_state(cfg: PagedKVConfig, dtype=jnp.bfloat16,
               sharding=None) -> Dict[str, jax.Array]:
    """Fresh storage pytree: k/v (L, n_blocks, block, K*hd), heads folded
    into the last axis (+ int8 scales (L, n_blocks, block, K)).

    ``sharding`` (optional ``jax.sharding.Sharding``) places every leaf —
    the model-parallel serving engine passes a NamedSharding that splits
    the folded last axis over the mesh's ``model`` axis on head
    boundaries, so each shard owns ``K / tp`` whole heads of every page
    and all writes/reads stay shard-local (the scale leaves share the same
    spec: their K axis lines up).
    """
    store_dtype = jnp.int8 if cfg.kv_quant == "int8" else dtype
    rows = (cfg.n_layers, cfg.n_blocks, cfg.block_size)
    shape = rows + (cfg.n_kv_heads * cfg.head_dim,)
    state = {"k": jnp.zeros(shape, store_dtype),
             "v": jnp.zeros(shape, store_dtype)}
    if cfg.kv_quant == "int8":
        sshape = rows + (cfg.n_kv_heads,)
        state["k_scale"] = jnp.ones(sshape, jnp.float32)
        state["v_scale"] = jnp.ones(sshape, jnp.float32)
    if sharding is not None:
        state = jax.device_put(state, sharding)
    return state


def write_prefill(state: Dict[str, jax.Array], kv_quant: str,
                  layer_kv: Tuple[jax.Array, jax.Array],
                  block_ids) -> Dict[str, jax.Array]:
    """Page out a whole prompt: k,v (L, T, K, hd) for ONE sequence, scattered
    into the sequence's blocks (T padded up to a block multiple)."""
    k, v = layer_kv
    bs = state["k"].shape[2]
    n_l, t = k.shape[:2]
    pad = (-t) % bs
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nb = k.shape[1] // bs
    kq, ks = quant_encode(k, kv_quant)
    vq, vs = quant_encode(v, kv_quant)
    # (L, T, K, hd | 1) -> the pool's (L, nb, block, K*hd | K) rows
    kq, vq, ks, vs = (None if a is None else a.reshape(n_l, nb, bs, -1)
                      for a in (kq, vq, ks, vs))
    ids = jnp.asarray(np.asarray(block_ids)[:nb], jnp.int32)
    out = dict(state)
    out["k"] = state["k"].at[:, ids].set(kq.astype(state["k"].dtype),
                                         mode="drop")
    out["v"] = state["v"].at[:, ids].set(vq.astype(state["v"].dtype),
                                         mode="drop")
    if ks is not None:
        out["k_scale"] = state["k_scale"].at[:, ids].set(ks, mode="drop")
        out["v_scale"] = state["v_scale"].at[:, ids].set(vs, mode="drop")
    return out


def write_token(state: Dict[str, jax.Array], kv_quant: str,
                layer_kv: Tuple[jax.Array, jax.Array],
                block_ids: jax.Array, offsets: jax.Array
                ) -> Dict[str, jax.Array]:
    """Decode append for ALL layers in one batched scatter.

    k,v (L, B, K, hd); block_ids/offsets (B,) map each sequence's next slot
    to (block, in-block offset). A block id >= n_blocks drops the update
    (used to mask inactive batch slots)."""
    k, v = layer_kv
    kq, ks = quant_encode(k, kv_quant)
    vq, vs = quant_encode(v, kv_quant)
    enc = {"k": kq, "v": vq}
    if ks is not None:
        enc["k_scale"], enc["v_scale"] = ks, vs
    return write_token_encoded(state, enc, block_ids, offsets)


def write_token_encoded(state: Dict[str, jax.Array],
                        enc: Dict[str, jax.Array],
                        block_ids: jax.Array, offsets: jax.Array
                        ) -> Dict[str, jax.Array]:
    """Like :func:`write_token` but with storage-ready values: ``enc`` holds
    already-encoded k/v (L, B, K, hd) (+ scales (L, B, K, 1)). Lets a
    caller that needed the quantized form anyway (the fused decode step
    attends to the fresh token as stored) skip a second quant_encode pass.
    Each of the L*B values lands as one (K*hd) row (K for a scale) at
    (layer, block, offset): a scatter into the pool in place."""
    n_l, bsz = enc["k"].shape[0], enc["k"].shape[1]
    li = jnp.repeat(jnp.arange(n_l), bsz)
    bi = jnp.tile(block_ids, n_l)
    oi = jnp.tile(offsets, n_l)
    out = dict(state)
    for key in enc:
        out[key] = state[key].at[li, bi, oi].set(
            enc[key].reshape(n_l * bsz, -1).astype(state[key].dtype),
            mode="drop")
    return out


def append_slots(table: jax.Array, positions: jax.Array, block_size: int,
                 n_blocks: int, valid: jax.Array
                 ) -> Tuple[jax.Array, jax.Array]:
    """Map per-row token positions to (block id, in-block offset) through a
    block table. ``table`` (B, max_blocks) int32, ``positions`` (B,) int32,
    ``valid`` (B,) bool. Rows flagged invalid route to block id ``n_blocks``
    — the dropped null write — so an inactive batch slot or a padded prompt
    chunk position can never corrupt live pages. Shared by the fused decode
    step (one row per sequence) and the chunked-prefill step (one row per
    chunk token of a single sequence)."""
    mb = table.shape[1]
    idx = jnp.clip(positions // block_size, 0, mb - 1)
    blk = jnp.take_along_axis(table, idx[:, None], axis=1)[:, 0]
    blk = jnp.where(valid, blk, n_blocks)
    return blk, positions % block_size


def truncate_slots(state: Dict[str, jax.Array], block_ids,
                   keep_tokens: int, block_size: int) -> Dict[str, jax.Array]:
    """Rewind ONE sequence's pages to a shorter valid prefix: every token
    slot at position >= ``keep_tokens`` within the sequence's blocks is
    reset to the never-written state (k/v zeroed, int8 scales restored to
    1.0) across all layers.

    Speculative decoding's exact-rollback contract rests on this: a
    rejected proposal must leave the cache bit-identical to a run that
    never speculated. The verify step already routes rejected appends to
    the null-write sentinel, so its pages never need scrubbing; this is
    the host-side API for the remaining rewind paths — recompute-style
    preemption scrubs the victim's pages before the allocator reuses them
    (``keep_tokens=0``), and tests use it as the rollback oracle."""
    ids = np.asarray(block_ids, np.int32)
    total = len(ids) * block_size
    if keep_tokens >= total:
        return state
    # Split the rewind into (a) the tail of the partially-kept boundary
    # block, scrubbed per-position, and (b) every wholly-scrubbed block,
    # reset with ONE block-granular set. The common keep_tokens=0 full
    # scrub (preemption, refcount-zero reclaim of a large cached pool) is
    # then O(blocks) instead of one O(blocks * block_size) scatter of
    # per-token indices; the values written are identical constants, so
    # the result is bitwise-identical to the per-position form.
    out = dict(state)
    first_whole = -(-keep_tokens // block_size)
    if keep_tokens % block_size:
        bnd = int(ids[keep_tokens // block_size])
        off = jnp.arange(keep_tokens % block_size, block_size,
                         dtype=jnp.int32)
        for key in state:
            fill = 1.0 if key.endswith("_scale") else 0.0
            # repro: allow[CACHE-01] host-validated allocator-owned ids; a bad scrub index must fail loudly, drop would mask it
            out[key] = out[key].at[:, bnd, off].set(
                jnp.asarray(fill, out[key].dtype))
    if first_whole < len(ids):
        whole = jnp.asarray(ids[first_whole:])
        for key in state:
            fill = 1.0 if key.endswith("_scale") else 0.0
            # repro: allow[CACHE-01] host-validated allocator-owned ids; a bad scrub index must fail loudly, drop would mask it
            out[key] = out[key].at[:, whole].set(
                jnp.asarray(fill, out[key].dtype))
    return out


def scrub_blocks(state: Dict[str, jax.Array],
                 block_ids) -> Dict[str, jax.Array]:
    """Reset whole blocks (any sequence) to the never-written state in one
    block-granular set per leaf — the scrub-on-reclaim path for the prefix
    cache's second-chance pool and the refcount-aware preemption scrub."""
    ids = jnp.asarray(np.asarray(block_ids, np.int32))
    out = dict(state)
    for key in state:
        fill = 1.0 if key.endswith("_scale") else 0.0
        # repro: allow[CACHE-01] host-validated allocator-owned ids; a bad scrub index must fail loudly, drop would mask it
        out[key] = state[key].at[:, ids].set(
            jnp.asarray(fill, state[key].dtype))
    return out


def copy_block(state: Dict[str, jax.Array], src: int, dst: int
               ) -> Dict[str, jax.Array]:
    """Copy one block's bytes (all layers, all leaves) src -> dst: the
    copy-on-write primitive — a request about to append into a shared or
    cache-registered block first duplicates it into a private one."""
    out = dict(state)
    for key in state:
        # repro: allow[CACHE-01] src/dst are host ints the allocator just handed out; a bad CoW target must fail loudly, not drop
        out[key] = state[key].at[:, dst].set(state[key][:, src])
    return out


def gather(state: Dict[str, jax.Array], layer: int, block_table: jax.Array,
           n_kv_heads: int, dtype=jnp.bfloat16
           ) -> Tuple[jax.Array, jax.Array]:
    """Dense per-batch view: block_table (B, max_blocks) int32 ->
    k,v (B, max_blocks*block, K, hd), the folded rows viewed per head.
    Legacy-path only; the fused step reads pages through the block table.

    Out-of-range table entries read as ZEROS: XLA's gather clamps indices,
    so a table row padded with the ``n_blocks`` null-write sentinel would
    otherwise silently alias the *last real block's* bytes — harmless only
    as long as every caller also masks by kv_len, which the fused read
    guarantees structurally and this path did not."""
    table = jnp.asarray(block_table)
    nb = state["k"].shape[1]
    in_range = (table >= 0) & (table < nb)       # (B, MB)
    safe = jnp.where(in_range, table, 0)

    def heads(key, hd):             # (B, MB, bs, K*hd) -> (B, MB, bs, K, hd)
        if key not in state:
            return None
        x = state[key][layer, safe]
        return x.reshape(x.shape[:3] + (n_kv_heads, hd))

    hd = state["k"].shape[-1] // n_kv_heads
    kq, vq = heads("k", hd), heads("v", hd)
    ks, vs = heads("k_scale", 1), heads("v_scale", 1)
    k = quant_decode(kq, ks, dtype)
    v = quant_decode(vq, vs, dtype)
    mask = in_range[:, :, None, None, None]
    k = jnp.where(mask, k, jnp.zeros((), k.dtype))
    v = jnp.where(mask, v, jnp.zeros((), v.dtype))
    b, mb, bs = k.shape[:3]
    return (k.reshape(b, mb * bs, *k.shape[3:]),
            v.reshape(b, mb * bs, *v.shape[3:]))


# ==========================================================================
# Object wrapper (host-side convenience for the legacy path and tests)
# ==========================================================================


class PagedKVCache:
    """Device storage: (L, n_blocks, block, K*hd) per k/v (+ int8 scales
    (L, n_blocks, block, K)); see the module docstring for the layout.
    Thin stateful wrapper over the pure functions above: every method
    rebinds ``self.state`` to the functionally-updated pytree.

    ``sharding`` (see :func:`init_state`) lays the pool out over a mesh —
    the model-parallel engine splits the folded head axis so every shard
    holds its whole heads of every page."""

    def __init__(self, cfg: PagedKVConfig, dtype=jnp.bfloat16,
                 sharding=None):
        self.cfg = cfg
        self.sharding = sharding
        self.state = init_state(cfg, dtype, sharding)

    # attribute views kept for existing call sites / tests
    @property
    def k(self) -> jax.Array:
        return self.state["k"]

    @property
    def v(self) -> jax.Array:
        return self.state["v"]

    @property
    def k_scale(self) -> Optional[jax.Array]:
        return self.state.get("k_scale")

    @property
    def v_scale(self) -> Optional[jax.Array]:
        return self.state.get("v_scale")

    # ---- quant helpers (compat shims over the pure fns) ----
    def _enc(self, x) -> Tuple[jax.Array, Optional[jax.Array]]:
        return quant_encode(x, self.cfg.kv_quant)

    def _dec(self, q, scale, dtype=jnp.bfloat16):
        return quant_decode(q, scale, dtype)

    # ---- updates ----
    def write_prefill(self, layer_kv: Tuple[jax.Array, jax.Array],
                      block_ids: List[int]) -> None:
        self.state = write_prefill(self.state, self.cfg.kv_quant,
                                   layer_kv, block_ids)

    def write_token(self, layer_kv: Tuple[jax.Array, jax.Array],
                    block_ids: jax.Array, offsets: jax.Array) -> None:
        self.state = write_token(self.state, self.cfg.kv_quant,
                                 layer_kv, block_ids, offsets)

    def truncate_slots(self, block_ids, keep_tokens: int) -> None:
        self.state = truncate_slots(self.state, block_ids, keep_tokens,
                                    self.cfg.block_size)

    def gather(self, layer: int, block_table: jax.Array,
               dtype=jnp.bfloat16) -> Tuple[jax.Array, jax.Array]:
        return gather(self.state, layer, block_table,
                      self.cfg.n_kv_heads, dtype)

    def hbm_bytes(self) -> int:
        n = self.k.size * self.k.dtype.itemsize * 2
        if self.k_scale is not None:
            n += self.k_scale.size * 4 * 2
        return int(n)
