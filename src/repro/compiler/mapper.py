"""HyperDex-analog model & memory mapper (compilation layer).

``plan_model(cfg, mesh_axes, mesh_shape, mode, ...)`` -> PhysicalPlan
``partition_specs(plan, axes_by_path)``               -> PartitionSpec tree

The mapper is model-and-hardware aware: given the logical architecture and
the mesh, it chooses head-wise attention tiles, column-wise FFN tiles,
padding to lane width (128) and TP degree, expert-parallel factorization,
FSDP axes for training, and emits the PartitionSpec rule table the jitted
programs use.  It is deliberately *deterministic and auditable* — the plan
is a JSON artifact, mirroring the paper's compiled memory map.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np
from jax.sharding import PartitionSpec as P

from repro.compiler.plan import (AttnPlan, MoEPlan, PhysicalPlan, _ceil_to,
                                 plan_attention)
from repro.configs.base import ArchConfig

LANE = 128  # TPU lane width; MXU tile edge


def plan_model(cfg: ArchConfig,
               mesh_axes: Optional[Sequence[str]],
               mesh_shape: Sequence[int],
               mode: str,
               *,
               esl_overlap: bool = True,
               esl_chunks: int = 4,
               seq_shard_kv: bool = False,
               kv_seq_axis: Optional[str] = None,
               remat: str = "block",
               scan_unroll: bool = False,
               use_kernels: bool = False,
               compute_dtype: str = "bfloat16",
               param_dtype: Optional[str] = None) -> PhysicalPlan:
    """Derive the physical plan for (arch x mesh x mode)."""
    if mesh_axes is None:
        mesh_axes_t: Optional[Tuple[str, ...]] = None
        mesh_shape_t: Tuple[int, ...] = (1,)
        tp, tp_axis = 1, None
        dp_axes: Tuple[str, ...] = ()
        fsdp_axes: Tuple[str, ...] = ()
    else:
        mesh_axes_t = tuple(mesh_axes)
        mesh_shape_t = tuple(int(s) for s in mesh_shape)
        assert mesh_axes_t[-1] == "model", "model axis must be innermost (ICI ring)"
        sizes = dict(zip(mesh_axes_t, mesh_shape_t))
        tp, tp_axis = sizes["model"], "model"
        dp_axes = tuple(a for a in mesh_axes_t if a != "model")
        # ZeRO-3: shard params over every non-model axis during training
        fsdp_axes = dp_axes if mode == "train" else ()

    if param_dtype is None:
        param_dtype = "float32" if mode == "train" else "bfloat16"

    attn = (plan_attention(cfg.n_heads, cfg.n_kv_heads, cfg.d_head, tp)
            if cfg.n_heads > 0 and not cfg.attention_free else None)
    if cfg.family == "rwkv":
        # attention-free, but time-mix is head-structured: shard heads
        attn = plan_attention(cfg.n_heads, cfg.n_heads, cfg.rwkv.head_dim, tp)

    d_ff_padded = _ceil_to(cfg.d_ff, max(tp * 8, LANE))
    d_ff_shard = d_ff_padded // tp
    vocab_padded = _ceil_to(cfg.vocab_size, max(tp * LANE, LANE))

    moe_plan = None
    if cfg.moe is not None:
        e = cfg.moe.n_experts
        if mode == "serve" and cfg.total_params() * 2 > 12e9 * tp:
            # giant MoE serving (llama4-400B): expand EP over data x model so
            # weights fit; attention stays model-parallel, experts use both.
            expert_axes: Tuple[str, ...] = tuple(
                a for a in ("data", "model") if mesh_axes_t and a in mesh_axes_t)
        else:
            expert_axes = ("model",) if tp_axis else ()
        ep_width = 1
        for a in expert_axes:
            ep_width *= dict(zip(mesh_axes_t, mesh_shape_t))[a]
        ep = math.gcd(e, ep_width) if ep_width > 1 else 1
        ffn_split = ep_width // ep if ep_width > 1 else 1
        dffe = _ceil_to(cfg.moe.d_ff_expert, max(ffn_split * 8, 8))
        moe_plan = MoEPlan(
            n_experts=e, ep=ep, ffn_split=ffn_split,
            experts_per_rank=e // ep,
            d_ff_expert_shard=dffe // max(ffn_split, 1),
            expert_axes=expert_axes,
            capacity_factor=cfg.moe.capacity_factor)

    rules = _rule_table(tp_axis, dp_axes, fsdp_axes, moe_plan, mode)

    return PhysicalPlan(
        arch=cfg.name, mode=mode, mesh_axes=mesh_axes_t,
        mesh_shape=mesh_shape_t, tp=tp, tp_axis=tp_axis, dp_axes=dp_axes,
        fsdp_axes=fsdp_axes, attn=attn, d_ff_shard=d_ff_shard,
        d_ff_padded=d_ff_padded, vocab_padded=vocab_padded, moe=moe_plan,
        esl_overlap=esl_overlap, esl_chunks=esl_chunks,
        seq_shard_kv=seq_shard_kv, kv_seq_axis=kv_seq_axis,
        remat=remat, scan_unroll=scan_unroll, use_kernels=use_kernels,
        compute_dtype=compute_dtype, param_dtype=param_dtype,
        cache_dtype=compute_dtype, rules=rules)


def _rule_table(tp_axis, dp_axes, fsdp_axes, moe_plan, mode) -> Dict[str, Any]:
    """logical axis -> mesh axes (None = replicated along that dim)."""
    fsdp = tuple(fsdp_axes) if fsdp_axes else None
    rules: Dict[str, Any] = {
        "embed": fsdp,                 # FSDP shards the d_model dim in train
        "embed_scatter": tp_axis,      # d_model dims that live scattered
        "vec": tp_axis,                # rank-local vector params (norms etc.)
        "vocab_rep": fsdp,             # untied input-embedding rows
        "q_heads": tp_axis,
        "kv_heads": tp_axis,
        "head_dim": None,
        "ffn": tp_axis,
        "vocab": tp_axis,
        "layers": None,
        "pos": None,
        "conv": None,
        "state": None,
        "lora": None,
        "dt": None,
        "mamba_inner": tp_axis,        # mamba d_inner: column tiles
        "rwkv_heads": tp_axis,
        "patches": None,
        None: None,
    }
    if moe_plan is not None:
        rules["experts"] = tuple(moe_plan.expert_axes) or None
        rules["expert_ffn"] = None     # split factor folded into expert axes
    return rules


def partition_specs(plan: PhysicalPlan,
                    axes_by_path: Dict[str, Tuple[Optional[str], ...]],
                    params_tree) -> Any:
    """Build a PartitionSpec pytree matching ``params_tree``.

    ``axes_by_path`` comes from InitCtx; paths are '/'-joined key chains.
    """
    import jax

    rules = plan.rules

    def spec_for(path: str, leaf) -> P:
        ax = axes_by_path.get(path)
        if ax is None:
            raise KeyError(f"no recorded axes for param path {path!r}; "
                           f"known={sorted(axes_by_path)[:8]}...")
        ndim = len(leaf.shape) if hasattr(leaf, "shape") else 0
        if len(ax) != ndim:
            raise ValueError(f"{path}: axes {ax} vs shape rank {ndim}")
        entries = []
        for a in ax:
            r = rules.get(a, None)
            entries.append(r)
        # PartitionSpec entries may be str | tuple | None
        return P(*entries)

    flat = jax.tree_util.tree_flatten_with_path(params_tree)[0]

    def path_str(kp) -> str:
        parts = []
        for k in kp:
            if hasattr(k, "key"):
                parts.append(str(k.key))
            elif hasattr(k, "idx"):
                parts.append(str(k.idx))
            else:
                parts.append(str(k))
        return "/".join(parts)

    spec_map = {path_str(kp): spec_for(path_str(kp), leaf)
                for kp, leaf in flat}

    def rebuild(tree):
        return jax.tree_util.tree_map_with_path(
            lambda kp, leaf: spec_map[path_str(kp)], tree)

    return rebuild(params_tree)


def summarize(plan: PhysicalPlan) -> Dict[str, Any]:
    """Human-readable mapper decisions (goes into EXPERIMENTS.md tables)."""
    out: Dict[str, Any] = {
        "arch": plan.arch, "mode": plan.mode, "tp": plan.tp,
        "d_ff_padded": plan.d_ff_padded, "vocab_padded": plan.vocab_padded,
        "esl_overlap": plan.esl_overlap,
    }
    if plan.attn:
        a = plan.attn
        out.update({
            "kv_shards": a.kv_shards, "dup": a.dup,
            "q_per_rank": a.q_per_rank, "kv_per_rank": a.kv_per_rank,
            "stored_q": a.hp, "stored_kv": a.gp,
            "q_pad_waste": round(a.waste_q, 3),
            "kv_storage_x": round(a.kv_storage_factor, 3),
        })
    if plan.moe:
        m = plan.moe
        out.update({"ep": m.ep, "ffn_split": m.ffn_split,
                    "experts_per_rank": m.experts_per_rank,
                    "expert_axes": m.expert_axes})
    return out
