"""Model registry: build init / forward / cache constructors per family.

``build_model(cfg, plan)`` returns a :class:`Model` whose members are pure
functions — the step builders in :mod:`repro.core.steps` wrap them in
``shard_map`` + ``jit`` with the mapper's PartitionSpecs.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compiler.mapper import partition_specs
from repro.core.dist import AxisEnv
from repro.models import attention as attn_mod
from repro.models import mamba as mamba_mod
from repro.models import rwkv as rwkv_mod
from repro.models import transformer as tf
from repro.models import whisper as wh
from repro.models.common import InitCtx

Params = Dict[str, Any]


@dataclass
class Model:
    cfg: Any
    plan: Any

    # ---- parameters -------------------------------------------------------

    def init(self, key: jax.Array, abstract: bool = False
             ) -> Tuple[Params, Dict[str, tuple]]:
        ctx = InitCtx(key, abstract=abstract,
                      param_dtype=jnp.dtype(self.plan.param_dtype))
        if self.cfg.family == "encdec":
            params = wh.init_encdec(ctx, self.cfg, self.plan)
        else:
            params = tf.init_lm(ctx, self.cfg, self.plan)
        return params, ctx.axes

    def abstract_params(self) -> Tuple[Params, Dict[str, tuple]]:
        return self.init(jax.random.PRNGKey(0), abstract=True)

    def param_specs(self):
        params, axes = self.abstract_params()
        return partition_specs(self.plan, axes, params), params

    # ---- forward ----------------------------------------------------------

    def forward(self, params: Params, tokens: jax.Array, *, env: AxisEnv,
                mode: str, positions=None, cache=None, frames=None,
                patch_embeds=None, block_tables=None, paged_kernel="auto",
                block_s=0, kv_valid_len=None, gather_fn=None):
        if self.cfg.family == "encdec":
            if block_s:
                raise ValueError(
                    "block_s override is not supported for encdec "
                    "decode (no paged/flash-chunk seam to tune)")
            if mode in ("chunk_prefill", "verify"):
                raise ValueError(f"mode={mode!r} needs the paged pool; "
                                 "encdec has no paged cache")
            return wh.forward_encdec(
                params, tokens, cfg=self.cfg, plan=self.plan, env=env,
                mode=mode, frames=frames, positions=positions, cache=cache,
                gather_fn=gather_fn)
        return tf.forward(
            params, tokens, cfg=self.cfg, plan=self.plan, env=env, mode=mode,
            positions=positions, cache=cache, patch_embeds=patch_embeds,
            block_tables=block_tables, paged_kernel=paged_kernel,
            block_s=block_s, kv_valid_len=kv_valid_len, gather_fn=gather_fn)

    # ---- decode cache -----------------------------------------------------

    def supports_paged_kv(self) -> bool:
        """Paged KV needs every layer to be attention (pure transformer):
        recurrent states (mamba/rwkv) are per-slot, not per-token."""
        cfg = self.cfg
        if cfg.family in ("rwkv", "encdec"):
            return False
        sb = tf.super_block_size(cfg)
        return all(cfg.is_attention_layer(j) for j in range(sb))

    def init_cache(self, batch: int, max_seq: int, *,
                   abstract: bool = False, dtype=None, paged: bool = False,
                   num_blocks: int = 0, block_size: int = 0,
                   scale_dtype=None):
        cfg, plan = self.cfg, self.plan
        dtype = dtype or jnp.dtype(plan.cache_dtype)
        if cfg.family == "encdec":
            return wh.init_encdec_cache(cfg, plan, batch, max_seq,
                                        dtype=dtype, abstract=abstract)
        if paged:
            assert self.supports_paged_kv(), \
                f"{cfg.name}: paged KV needs an attention-only stack"
        n_sb = tf.n_super_blocks(cfg)
        sb = tf.super_block_size(cfg)

        def stack(tree):
            return jax.tree.map(
                lambda s: (jax.ShapeDtypeStruct((n_sb,) + s.shape, s.dtype)
                           if abstract else
                           jnp.zeros((n_sb,) + s.shape, s.dtype)),
                tree,
                is_leaf=lambda x: isinstance(x, (jax.ShapeDtypeStruct,
                                                 jax.Array)))

        kv_w = plan_kv_seq_width(plan)
        out = {}
        for j in range(sb):
            if cfg.family == "rwkv":
                c = rwkv_mod.init_rwkv_state(cfg, plan, batch,
                                             abstract=True, dtype=dtype)
            elif cfg.is_attention_layer(j):
                c = attn_mod.init_cache(plan, batch, max_seq, dtype=dtype,
                                        abstract=True, kv_seq_width=kv_w,
                                        paged=paged, num_blocks=num_blocks,
                                        block_size=block_size,
                                        scale_dtype=scale_dtype)
            else:
                c = mamba_mod.init_mamba_state(cfg, plan, batch,
                                               abstract=True, dtype=dtype)
            out[f"l{j}"] = stack(c)
        return out

    def cache_specs(self, env: AxisEnv, paged: bool = False,
                    kv_quant: bool = False):
        """PartitionSpec tree matching init_cache (decoder-only families).

        ``paged=True`` describes the shared block pool: stacked per-layer
        leaves are (n_sb, num_blocks, Gp, block_size, dh) with the stored
        kv heads (Gp) sharded over the model ring — each rank holds its
        head shard of EVERY block, so one host-side block table drives
        all ranks and pool bytes split 1/tp per rank.  ``kv_quant=True``
        adds the quantized pool's scale side-array specs (same layout
        minus the d_head axis, heads likewise ring-sharded).
        """
        cfg, plan = self.cfg, self.plan
        dp = tuple(env.dp) if env.dp else None
        m = plan.tp_axis
        scat = m if plan.esl_overlap else None
        kv_w = plan_kv_seq_width(plan)

        if cfg.family == "encdec":
            kv = P(None, dp, None, m, None)
            return {"k": kv, "v": kv, "ck": kv, "cv": kv}

        sb = tf.super_block_size(cfg)
        out = {}
        for j in range(sb):
            if cfg.family == "rwkv":
                out[f"l{j}"] = {"shift_t": P(None, dp, None, scat),
                                "shift_c": P(None, dp, None, scat),
                                "wkv": P(None, dp, m, None, None)}
            elif cfg.is_attention_layer(j):
                if paged:
                    out[f"l{j}"] = {"k": P(None, None, m, None, None),
                                    "v": P(None, None, m, None, None)}
                    if kv_quant:
                        out[f"l{j}"]["k_scale"] = P(None, None, m, None)
                        out[f"l{j}"]["v_scale"] = P(None, None, m, None)
                elif kv_w > 1:
                    out[f"l{j}"] = {"k": P(None, dp, env.kv_seq_axis, None,
                                           m, None),
                                    "v": P(None, dp, env.kv_seq_axis, None,
                                           m, None)}
                else:
                    out[f"l{j}"] = {"k": P(None, dp, None, m, None),
                                    "v": P(None, dp, None, m, None)}
            else:
                out[f"l{j}"] = {"conv": P(None, dp, None, m),
                                "ssm": P(None, dp, m, None)}
        return out


def plan_kv_seq_width(plan) -> int:
    if getattr(plan, "kv_seq_axis", None):
        return dict(zip(plan.mesh_axes, plan.mesh_shape))[plan.kv_seq_axis]
    return 1


def build_model(cfg, plan) -> Model:
    return Model(cfg=cfg, plan=plan)
