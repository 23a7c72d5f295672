"""Sharding engine: ZeRO stages, Megatron TP/SP, expert parallelism, and
host offload — expressed as PartitionSpec resolution over logical axes.

Two halves:

* :class:`ShardCtx` — runtime context the model blocks use to place
  activation sharding constraints (``constrain(x, kind)``) and to drive the
  MoE expert-parallel all-to-all.

* :func:`state_shardings` — resolves NamedShardings for parameters,
  gradients and optimizer state from (a) each weight's logical axes, (b) the
  technique's ZeRO stage and TP/offload flags, and (c) divisibility against
  the actual mesh. This is where the paper's §II-E semantics live:

    ZeRO-1: optimizer state sharded over DP          -> all-gather on update
    ZeRO-2: + gradients sharded                      -> reduce-scatter in bwd
    ZeRO-3: + parameters sharded                     -> all-gather at use
    +O    : sharded state placed in pinned host mem  -> H<->D transfers
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.config import ArchConfig, Technique
from repro.models.params import ParamSpec, tree_paths, logical_axes


# ==========================================================================
# ShardCtx: activation constraints + EP context
# ==========================================================================


@dataclasses.dataclass
class ShardCtx:
    mesh: Optional[Mesh]
    dp_axes: Tuple[str, ...]            # e.g. ("pod","data") / ("data","model")
    model_axis: Optional[str]           # "model" or None (dp_over_model)
    attn_mode: str                      # "head" | "seq"
    technique: Technique = Technique()
    cfg: Optional[ArchConfig] = None

    # -- helpers --
    def axis_size(self, name: Optional[str]) -> int:
        if name is None or self.mesh is None:
            return 1
        return self.mesh.shape[name]

    @property
    def dp_size(self) -> int:
        return int(np.prod([self.axis_size(a) for a in self.dp_axes]) or 1)

    @property
    def dp_spec_entry(self):
        return self.dp_axes if len(self.dp_axes) > 1 else (
            self.dp_axes[0] if self.dp_axes else None)

    @property
    def technique_disables_ep(self) -> bool:
        return not self.technique.tp

    def _dp(self, dim: int):
        """Largest dp prefix that divides `dim`."""
        axes = []
        prod = 1
        for a in self.dp_axes:
            prod *= self.axis_size(a)
            axes.append(a)
        while axes and dim % int(np.prod([self.axis_size(a) for a in axes])):
            axes.pop()
        if not axes:
            return None
        return tuple(axes) if len(axes) > 1 else axes[0]

    def _mdl(self, dim: int):
        m = self.model_axis
        if m is None or not self.technique.tp or dim % self.axis_size(m):
            return None
        return m

    def spec_for(self, kind: str, shape: Tuple[int, ...]) -> P:
        t = self.technique
        seq = self.attn_mode == "seq"
        sp_t = self._mdl(shape[1]) if (t.sp and len(shape) > 1) else None
        if kind == "hidden":
            return P(self._dp(shape[0]), sp_t, None)
        if kind == "act_q":
            if seq:
                return P(self._dp(shape[0]), self._mdl(shape[1]), None, None)
            return P(self._dp(shape[0]), None, self._mdl(shape[2]), None)
        if kind == "act_kv":
            if seq:
                return P(self._dp(shape[0]), self._mdl(shape[1]), None, None)
            return P(self._dp(shape[0]), None, self._mdl(shape[2]), None)
        if kind == "act_ffn":
            return P(self._dp(shape[0]), None, self._mdl(shape[2]))
        if kind == "act_ssm":
            return P(self._dp(shape[0]), None, self._mdl(shape[2]))
        if kind == "ssm_x":
            return P(self._dp(shape[0]), None, self._mdl(shape[2]), None)
        if kind == "ssm_dt":
            return P(self._dp(shape[0]), None, self._mdl(shape[2]))
        if kind == "ssm_bc":
            return P(self._dp(shape[0]), None, None, None)
        if kind == "logits":
            return P(self._dp(shape[0]), None, self._mdl(shape[2]))
        if kind == "head":
            return P(None, self._mdl(shape[1]))
        if kind == "kv_cache":
            # head mode splits the KV-head axis (each shard owns K/tp heads
            # of the whole sequence — matches head-sharded attention reads);
            # seq mode — or a head count the degree does not divide — splits
            # the sequence axis instead (context parallelism): an
            # indivisible head axis must NOT fall back to replication, which
            # would multiply per-device cache memory by the TP degree
            if not seq and self._mdl(shape[2]) is not None:
                return P(self._dp(shape[0]), None, self._mdl(shape[2]), None)
            return P(self._dp(shape[0]), self._mdl(shape[1]), None, None)
        if kind == "kv_cache_stack":
            if not seq and self._mdl(shape[3]) is not None:
                return P(None, self._dp(shape[1]), None, self._mdl(shape[3]),
                         None)
            return P(None, self._dp(shape[1]), self._mdl(shape[2]),
                     None, None)
        if kind == "tokens":
            return P(self._dp(shape[0]), None)
        if kind == "kv_pool":
            # paged serving storage (L, n_blocks, block, K*hd), kv heads
            # folded into the last axis (int8 scales: (L, n_blocks, block,
            # K)): split that axis only where the degree divides the kv-head
            # count, so every shard owns K/tp whole heads of every page, and
            # replicate otherwise; block tables and the allocator stay
            # host-global, and the engine's scatters/gathers are shard-local
            # by construction
            return P(None, None, None, self._mdl(self.cfg.n_kv_heads))
        raise KeyError(kind)

    def constrain(self, x: jax.Array, kind: str) -> jax.Array:
        if self.mesh is None:
            return x
        spec = self.spec_for(kind, x.shape)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    def batch_sharding(self, ndim: int = 2) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(
            self.mesh, P(self.dp_spec_entry, *([None] * (ndim - 1))))


def make_shard_ctx(cfg: ArchConfig, technique: Technique,
                   mesh: Optional[Mesh]) -> ShardCtx:
    if mesh is None:
        return ShardCtx(None, (), None, "head", technique, cfg)
    names = list(mesh.axis_names)
    model_axis = "model" if "model" in names else None
    dp = tuple(a for a in ("pod", "data") if a in names)
    if cfg.dp_over_model and model_axis:
        dp = dp + (model_axis,)
        model_axis = None
    if not technique.tp:
        if model_axis:                      # fold unused model axis into DP
            dp = dp + (model_axis,)
        model_axis = None
    msize = mesh.shape[model_axis] if model_axis else 1
    if technique.attn_mode != "auto":
        attn_mode = technique.attn_mode
    else:
        attn_mode = "head" if (cfg.n_heads == 0 or msize <= 1
                               or cfg.n_heads % msize == 0) else "seq"
    return ShardCtx(mesh, dp, model_axis, attn_mode, technique, cfg)


def make_serving_ctx(cfg: ArchConfig, mesh: Mesh) -> ShardCtx:
    """Model-axis TP context for the serving engine.

    Serving shards only over the mesh's ``model`` axis: the scheduler,
    block tables and batch slots are host-global (policy is device-count-
    agnostic), so there is no data axis — the batch is replicated and every
    collective the steps emit is a model-axis psum/all-gather at the
    row-parallel seams (wo, MLP down-proj, logits). Attention is pinned to
    head mode: the paged KV pool splits its folded KV-head axis on head
    boundaries (``kv_pool``) and each shard computes complete (o, m, l)
    partials for its own heads — LSE-merging via merge_partials stays
    shard-local, never a collective.
    Axes that don't divide the TP degree (e.g. 4 smoke KV heads at tp=8)
    degrade to replication per tensor, not an error, exactly like training.
    """
    if mesh is None:
        return None
    if "model" not in mesh.axis_names:
        raise ValueError(f"serving mesh needs a 'model' axis, got "
                         f"{mesh.axis_names}")
    return ShardCtx(mesh, dp_axes=(), model_axis="model", attn_mode="head",
                    technique=Technique(tp=True), cfg=cfg)


# ==========================================================================
# Parameter / optimizer-state sharding resolution
# ==========================================================================

# logical axis -> model-axis eligibility under TP
_TP_AXES = {"q_heads", "mlp", "experts", "ssm_inner", "ssm_heads"}
_TP_AXES_COND = {"kv_heads"}     # only if the head *count* divides the axis
_HEAD_VOCAB = {"vocab"}          # vocab sharded over model only for `head`


def _tp_entry(ctx: ShardCtx, name: Optional[str], dim: int, path: str):
    if name is None or ctx.model_axis is None or not ctx.technique.tp:
        return None
    m, msz = ctx.model_axis, ctx.axis_size(ctx.model_axis)
    if dim % msz:
        return None
    if name in _TP_AXES:
        if name == "q_heads" and ctx.attn_mode == "seq":
            return None
        if name == "ssm_heads":
            return None  # small vectors (A, D, dt_bias): replicate
        return m
    if name in _TP_AXES_COND:
        if ctx.attn_mode == "seq":
            return None
        return m if (ctx.cfg and ctx.cfg.n_kv_heads % msz == 0) else None
    if name == "vocab" and "head" in path:
        return m
    return None


def _zero_overlay(entries, shape, logical, ctx: ShardCtx):
    """Add DP axes to the best unsharded dim (FSDP/ZeRO sharding)."""
    order = sorted(range(len(shape)), key=lambda i: -shape[i])
    # prefer non-layer dims; a sharded scan dim forces per-layer gathers of
    # the *stacked* tensor which GSPMD handles poorly
    order = [i for i in order if logical[i] != "layers"] + \
            [i for i in order if logical[i] == "layers"]
    for i in order:
        if entries[i] is not None:
            continue
        dp = ctx._dp(shape[i])
        if dp is not None:
            entries[i] = dp
            return entries
    return entries


_TP_PRIORITY = {"experts": 0, "q_heads": 1, "kv_heads": 1, "mlp": 2,
                "ssm_inner": 2, "vocab": 3}


def resolve_spec(ctx: ShardCtx, path: str, shape: Tuple[int, ...],
                 logical: Tuple[Optional[str], ...], *, zero: bool) -> P:
    entries = [None] * len(shape)
    candidates = []
    for i, (name, dim) in enumerate(zip(logical, shape)):
        if _tp_entry(ctx, name, dim, path) is not None:
            candidates.append((_TP_PRIORITY.get(name, 9), i))
    if candidates:  # the model axis may shard at most one dim
        _, best = min(candidates)
        entries[best] = ctx.model_axis
    if zero:
        entries = _zero_overlay(entries, shape, logical, ctx)
    return P(*entries)


_SUFFIXES = re.compile(r"\.(a|b|base|data|scale|scale2)|\[\d+\]$")


def _normalize_path(path: str) -> Tuple[str, str]:
    """Split a state path into (base param path, special suffix), stripping
    optimizer-tree prefixes so m/v/master leaves inherit the weight's spec."""
    for prefix in ("['m']", "['v']", "['master']", "['params']"):
        if path.startswith(prefix):
            path = path[len(prefix):]
            break
    suffix = ""
    for tok in (".a", ".b", ".base", ".data", ".scale2", ".scale",
                ".q"):  # .q/.scale: Opt8 block-quantized moments
        if tok in path:
            base, _, rest = path.partition(tok)
            return base, tok[1:]
    return path, suffix


def state_shardings(ctx: ShardCtx, state_tree, logical_by_path: Dict[str, tuple],
                    *, component: str):
    """NamedSharding tree for `state_tree` (params / grads / opt m / opt v).

    component: 'params' | 'grads' | 'opt'. ZeRO overlay applies when
      params: stage>=3, grads: stage>=2, opt: stage>=1.
    Offload (+O) puts opt state (and ZeRO-3 params) in pinned host memory.
    """
    t = ctx.technique
    stage = t.zero_stage
    zero = {"params": stage >= 3, "grads": stage >= 2,
            "opt": stage >= 1}[component]
    host = t.offload and (
        component == "opt" or (component == "params" and stage >= 3))
    mem_kind = "pinned_host" if host else None

    def resolve(path_keys, leaf):
        if leaf is None:
            return None
        pstr = jax.tree_util.keystr(path_keys)
        base, suffix = _normalize_path(pstr)
        logical = logical_by_path.get(base)
        shape = tuple(leaf.shape)
        if suffix in ("a", "b") or logical is None:
            entries = [None] * len(shape)
            if zero:
                entries = _zero_overlay(entries, shape,
                                        ("?",) * len(shape), ctx)
            spec = P(*entries)
        elif suffix in ("scale", "scale2"):
            spec = P(*([None] * len(shape)))
        elif suffix == "data" and len(shape) != len(logical):
            # nf4-packed flat storage: dp overlay only
            entries = [None] * len(shape)
            if zero:
                entries = _zero_overlay(entries, shape,
                                        ("?",) * len(shape), ctx)
            spec = P(*entries)
        else:
            spec = resolve_spec(ctx, base, shape, logical, zero=zero)
        kw = {"memory_kind": mem_kind} if mem_kind else {}
        return NamedSharding(ctx.mesh, spec, **kw)

    return jax.tree_util.tree_map_with_path(resolve, state_tree)


def logical_by_path_of(spec_tree) -> Dict[str, tuple]:
    return {path: ps.logical for path, ps in tree_paths(spec_tree)}
