"""Pure-jnp oracle for fused decode (flash-decode) attention."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def gather_kv_pages(pages: jax.Array, block_tables: jax.Array,
                    layer: jax.Array | None = None) -> jax.Array:
    """Materialize the contiguous per-request view of a paged KV pool.

    pages: (N, G, bs, dh) head-major block pool, or a quantized pool's
    (N, G, bs) scale side-array; block_tables: (B, T) physical block id
    per logical block (entries past the used length point at the
    reserved null block 0 and are masked by ``lengths`` downstream).
    ``layer``: ``pages`` is the layers' stack (L, N, ...) and the view is
    of layer ``layer`` (gathered straight from the stack).
    Returns the seq-major view (B, T*bs, G, dh) (scales: (B, T*bs, G)).
    """
    B, T = block_tables.shape
    g = pages[block_tables] if layer is None else pages[layer, block_tables]
    g = jnp.moveaxis(g, 3, 2)                     # (B, T, bs, G[, dh])
    return g.reshape(B, T * g.shape[2], *g.shape[3:])


def paged_decode_attention_ref(q: jax.Array, k_pages: jax.Array,
                               v_pages: jax.Array, block_tables: jax.Array,
                               lengths: jax.Array, *,
                               layer: jax.Array | None = None,
                               k_new: jax.Array | None = None,
                               v_new: jax.Array | None = None,
                               k_scale: jax.Array | None = None,
                               v_scale: jax.Array | None = None
                               ) -> jax.Array:
    """Oracle paged decode attention: gather blocks, run the dense oracle.

    q: (B,H,dh); k_pages,v_pages: (N,G,bs,dh) with H = G*gs;
    block_tables: (B,T); lengths: (B,).  ``layer``: the pools are the
    layers' stacks (L,N,G,bs,dh) and layer ``layer`` is attended.
    ``k_scale/v_scale`` ((N,G,bs)) dequantize a quantized pool's gathered
    view.  ``k_new/v_new`` ((B,G,dh)) are attended at position
    ``lengths`` in addition to the resident rows, as the kernel's fold
    does.
    """
    H = q.shape[1]
    gs = max(H // k_pages.shape[-3], 1)
    k = gather_kv_pages(k_pages, block_tables, layer)
    v = gather_kv_pages(v_pages, block_tables, layer)
    if k_scale is not None:
        k = k.astype(jnp.float32) * gather_kv_pages(
            k_scale, block_tables, layer).astype(jnp.float32)[..., None]
        v = v.astype(jnp.float32) * gather_kv_pages(
            v_scale, block_tables, layer).astype(jnp.float32)[..., None]
    k = jnp.repeat(k, gs, axis=2)[:, :, :H]
    v = jnp.repeat(v, gs, axis=2)[:, :, :H]
    if k_new is None:
        return decode_attention_ref(q, k, v, lengths)
    # mask-scatter the new token at its position in the gathered view,
    # extend the valid length by one
    kn = jnp.repeat(k_new, gs, axis=1)[:, :H]
    vn = jnp.repeat(v_new, gs, axis=1)[:, :H]

    def put(view, row, pos):
        return jax.lax.dynamic_update_slice(
            view, row[None].astype(view.dtype), (pos, 0, 0))
    k = jax.vmap(put)(k, kn, lengths)
    v = jax.vmap(put)(v, vn, lengths)
    return decode_attention_ref(q, k, v, lengths + 1)


def decode_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                         lengths: jax.Array) -> jax.Array:
    """q: (B,H,dh); k,v: (B,S,H,dh); lengths: (B,) valid cache length.

    Softmax over positions [0, length); f32 accumulation.
    """
    B, S, H, dh = k.shape
    scale = 1.0 / math.sqrt(dh)
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = jnp.arange(S)[None, None, :] < lengths[:, None, None]
    s = jnp.where(mask, s, jnp.finfo(jnp.float32).min / 2)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhs,bshd->bhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
