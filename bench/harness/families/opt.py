"""The OPT family: weights from a seed, the program's configuration, the
plain float32 reference forward, and the operations and bytes of a
decode step (the contract is in ``harness/families/__init__.py``).

The benchmark makes the weights itself, in a layout of its own (HF OPT's
names and ``x @ W`` orientation, layers stacked), in one jitted call on
the device, in the dtype they are served in.  :func:`program_params`
lays them out as the serving program's parameter tree; the reference
regenerates them from the same seed after the program is gone, so it
takes nothing the program has made.

The reference follows OPT (arXiv:2205.01068; HF ``OPTForCausalLM`` with
``do_layer_norm_before=True``): learned absolute positions, pre-norm
LayerNorm (eps 1e-5), biased q/k/v/out projections, a ReLU MLP with
biases, a final LayerNorm and an output head tied to the input
embedding.  Positions are indexed from 0 (HF adds an offset of 2 to the
same table; with random weights that only renames rows).  The serving
program has no out-projection bias, so the seeded out-projection bias
is zero, in the program and in the reference alike.

The control (``served_gaps(..., control=True)``; ``"int8"`` inside)
computes every matrix product in int8: the projections and the head
from int8 weights (per output column) and int8 activations (per row),
and attention from int8 queries, keys, probabilities and values (each
scaled per vector along the contracted dimension); symmetric absmax
throughout.  That is the step below the bfloat16 the configurations
serve in.

Operations and bytes: ``ctx`` is the list of resident token counts of
the live slots at one decode step (each slot attends its ``p`` resident
tokens plus the new one).  Bytes are what the algorithm has to move,
not what a kernel happens to move: weights once per step, the live KV
blocks once per layer, and the new token's K and V written once.  The
vocabulary is ``V`` rows (the program's padding is not work).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
LN_EPS = 1e-5
BIAS_STD = 0.1        # biases and LayerNorm offsets: large enough that a
                      # path that drops one fails the comparison
DECODE_KERNEL = "paged_decode_attention"


def dims(model: dict) -> Dict[str, int]:
    """The sizes the benchmark needs, from a configuration's HF keys."""
    d = dict(L=model["num_hidden_layers"], D=model["hidden_size"],
             H=model["num_attention_heads"], F=model["ffn_dim"],
             V=model["vocab_size"], P=model["max_position_embeddings"])
    if d["D"] % d["H"]:
        raise ValueError("hidden_size must divide into the heads")
    d["dh"] = d["D"] // d["H"]
    return d


def arch_config(conf: dict, d: Dict[str, int]):
    """The serving program's ``ArchConfig``: the registry's entry for
    ``conf["arch"]`` at the configuration's sizes."""
    from repro.configs import get_config
    return dataclasses.replace(
        get_config(conf["arch"]), name=conf["name"], n_layers=d["L"],
        d_model=d["D"], n_heads=d["H"], n_kv_heads=d["H"], d_head=d["dh"],
        d_ff=d["F"], vocab_size=d["V"], max_seq=d["P"])


def _shapes(d: Dict[str, int]) -> Dict[str, tuple]:
    L, D, F = d["L"], d["D"], d["F"]
    return {
        "embed": (d["V"], D), "pos": (d["P"], D),
        "ln1_g": (L, D), "ln1_b": (L, D),
        "wq": (L, D, D), "bq": (L, D), "wk": (L, D, D), "bk": (L, D),
        "wv": (L, D, D), "bv": (L, D), "wo": (L, D, D), "bo": (L, D),
        "ln2_g": (L, D), "ln2_b": (L, D),
        "w1": (L, D, F), "b1": (L, F), "w2": (L, F, D), "b2": (L, D),
        "lnf_g": (D,), "lnf_b": (D,),
    }


def _std(name: str, d: Dict[str, int]) -> float:
    """Scales that keep a random model's greedy tokens varied: logits of
    std ~3, attention scores of std ~4 (a peaked, position-aware softmax
    rather than a uniform average) and positions as loud as a layer's
    output.  Weaker query/key or position scales make the model repeat
    its last token, which a decode path that drops its KV writes would
    pass unseen."""
    if name == "embed":
        return 3.0 / math.sqrt(d["D"])
    if name == "pos":
        return 1.0
    if name in ("wq", "wk"):
        return 2.0 / math.sqrt(d["D"])
    if name in ("wv", "wo", "w1"):
        return 1.0 / math.sqrt(d["D"])
    if name == "w2":
        return 1.0 / math.sqrt(d["F"])
    return BIAS_STD


def canonical(key: jax.Array, d: Dict[str, int], dtype=jnp.bfloat16
              ) -> Dict[str, jax.Array]:
    """Every OPT weight from ``key`` (jittable; one leaf per name)."""
    out = {}
    for i, (name, shape) in enumerate(sorted(_shapes(d).items())):
        if name == "bo":
            out[name] = jnp.zeros(shape, dtype)
            continue
        x = jax.random.normal(jax.random.fold_in(key, i), shape,
                              jnp.float32) * _std(name, d)
        if name.endswith("_g"):
            x = x + 1.0
        out[name] = x.astype(dtype)
    return out


def program_params(c: Dict[str, jax.Array], d: Dict[str, int],
                   template) -> dict:
    """The serving program's parameter tree (the OPT layout of
    ``repro.models.transformer.init_lm``) from the canonical weights.
    ``template`` is that tree's shapes; a leaf that does not match is an
    error, not a guess."""
    L, D, H, dh = d["L"], d["D"], d["H"], d["dh"]
    v_pad = template["embed"].shape[0]
    attn = {"wq": c["wq"].reshape(L, D, H, dh),
            "wk": c["wk"].reshape(L, D, H, dh),
            "wv": c["wv"].reshape(L, D, H, dh),
            "wo": c["wo"].reshape(L, H, dh, D),
            "bq": c["bq"].reshape(L, H, dh),
            "bk": c["bk"].reshape(L, H, dh),
            "bv": c["bv"].reshape(L, H, dh)}
    tree = {
        "embed": jnp.pad(c["embed"], ((0, v_pad - d["V"]), (0, 0))),
        "pos_embed": c["pos"],
        "ln_f": {"scale": c["lnf_g"], "bias": c["lnf_b"]},
        "blocks": {"l0": {
            "ln1": {"scale": c["ln1_g"], "bias": c["ln1_b"]},
            "ln2": {"scale": c["ln2_g"], "bias": c["ln2_b"]},
            "attn": attn,
            "mlp": {"wi": c["w1"], "bi": c["b1"], "wd": c["w2"],
                    "bd": c["b2"]}}},
    }
    want = jax.tree_util.tree_structure(template)
    got = jax.tree_util.tree_structure(tree)
    if want != got:
        raise ValueError(f"parameter tree differs from the program's:\n"
                         f"{got}\nvs\n{want}")
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(tree)[0],
                            jax.tree.leaves(template)):
        if tuple(a.shape) != tuple(b.shape) or a.dtype != b.dtype:
            raise ValueError(f"{jax.tree_util.keystr(path)}: {a.shape} "
                             f"{a.dtype} vs the program's {b.shape} "
                             f"{b.dtype}")
    return tree


# -- the reference -------------------------------------------------------

def _fq(x: jax.Array, axis: int) -> jax.Array:
    """Symmetric int8 fake-quantization along ``axis`` (absmax scale)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _mm(x, w, control: Optional[str]):
    if control == "int8":
        x = _fq(x, -1)
        w = _fq(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _att(q, k, v, mask, control: Optional[str]):
    """Softmax attention; ``q, k, v``: (B, T, H, dh)."""
    if control == "int8":
        q, k = _fq(q, -1), _fq(k, -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) \
        / math.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    if control == "int8":
        p, v = _fq(p, -1), _fq(v, 1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI)


def _ln(x, g, b):
    m = jnp.mean(x, -1, keepdims=True)
    v = jnp.mean(jnp.square(x - m), -1, keepdims=True)
    return (x - m) * lax.rsqrt(v + LN_EPS) * g + b


@partial(jax.jit, static_argnames=("H", "control"))
def _layer(c, l, x, valid, *, H: int, control: Optional[str]):
    f32 = lambda n: lax.dynamic_index_in_dim(c[n], l, 0, False) \
        .astype(jnp.float32)
    B, T, D = x.shape
    dh = D // H
    h = _ln(x, f32("ln1_g"), f32("ln1_b"))
    q = (_mm(h, f32("wq"), control) + f32("bq")).reshape(B, T, H, dh)
    k = (_mm(h, f32("wk"), control) + f32("bk")).reshape(B, T, H, dh)
    v = (_mm(h, f32("wv"), control) + f32("bv")).reshape(B, T, H, dh)
    i = jnp.arange(T)
    mask = (i[None, :] <= i[:, None])[None, None] & valid[:, None, None, :]
    a = _att(q, k, v, mask, control).reshape(B, T, D)
    x = x + _mm(a, f32("wo"), control) + f32("bo")
    h = _ln(x, f32("ln2_g"), f32("ln2_b"))
    h = jax.nn.relu(_mm(h, f32("w1"), control) + f32("b1"))
    return x + _mm(h, f32("w2"), control) + f32("b2")


@jax.jit
def _embed(c, tokens):
    T = tokens.shape[1]
    return (c["embed"][tokens].astype(jnp.float32)
            + c["pos"][:T][None].astype(jnp.float32))


@partial(jax.jit, static_argnames=("control",))
def _head(c, x, *, control: Optional[str]):
    x = _ln(x, c["lnf_g"].astype(jnp.float32), c["lnf_b"].astype(jnp.float32))
    return _mm(x, c["embed"].astype(jnp.float32).T, control)


def hidden(c, d, tokens: np.ndarray, lens: np.ndarray,
           control: Optional[str] = None) -> jax.Array:
    """Final hidden states (B, T, D) of the padded batch ``tokens``,
    computed a layer at a time."""
    tokens = jnp.asarray(tokens, jnp.int32)
    valid = jnp.arange(tokens.shape[1])[None] < jnp.asarray(lens)[:, None]
    x = _embed(c, tokens)
    for l in range(d["L"]):
        x = _layer(c, jnp.int32(l), x, valid, H=d["H"], control=control)
    return x


@jax.jit
def _gaps(ref_logits, other_logits, targets):
    """Per position: the reference's best logit minus its logit for the
    target token (``targets >= 0``) and for ``other``'s first choice."""
    best = jnp.max(ref_logits, -1)
    tgt = jnp.take_along_axis(ref_logits, jnp.maximum(targets, 0)[..., None],
                              -1)[..., 0]
    alt = jnp.take_along_axis(
        ref_logits, jnp.argmax(other_logits, -1)[..., None], -1)[..., 0]
    return best - tgt, best - alt


def served_gaps(c, d, seqs, control: bool = False):
    """For each ``(prompt, served)`` pair: the gap, at every served
    position, between the reference's best logit and its logit for the
    served token (teacher-forced on the served tokens).  With
    ``control``, also the gap of the token the control would put first.

    Returns ``(served_gap, control_gap)`` as flat numpy arrays
    (``control_gap`` is None without a control)."""
    V = d["V"]
    T = max(len(p) + len(s) - 1 for p, s in seqs)
    T = -(-T // 128) * 128
    B = len(seqs)
    tokens = np.zeros((B, T), np.int32)
    targets = np.full((B, T), -1, np.int32)
    lens = np.zeros((B,), np.int32)
    for b, (p, s) in enumerate(seqs):
        full = list(p) + list(s[:-1])
        tokens[b, :len(full)] = full
        lens[b] = len(full)
        targets[b, len(p) - 1:len(p) - 1 + len(s)] = s
    ref = _head(c, hidden(c, d, tokens, lens), control=None)[..., :V]
    other = ref
    if control:
        other = _head(c, hidden(c, d, tokens, lens, "int8"),
                      control="int8")[..., :V]
    g_served, g_other = _gaps(ref, other, jnp.asarray(targets))
    sel = targets >= 0
    g_served = np.asarray(g_served)[sel]
    g_other = np.asarray(g_other)[sel] if control else None
    return g_served, g_other


# -- operations and bytes of a decode step ----------------------------------

def weight_params(d: Dict[str, int]) -> int:
    """Parameters one decode step streams (head included, position
    table and embedding gather excluded: one row each)."""
    L, D, F, V = d["L"], d["D"], d["F"], d["V"]
    per_layer = 4 * D * D + 2 * D * F + 4 * D + F + D + 4 * D
    return L * per_layer + V * D + 2 * D


def matmul_params(d: Dict[str, int]) -> int:
    """Parameters that take part in a multiply-add per token."""
    L, D, F, V = d["L"], d["D"], d["F"], d["V"]
    return L * (4 * D * D + 2 * D * F) + V * D


def blocks(p: int, block: int) -> int:
    return -(-p // block)


def kv_read_bytes(d: Dict[str, int], ctx: Sequence[int], block: int,
                  itemsize: int = 2) -> int:
    """K and V of the live slots' resident blocks, every layer."""
    per_tok = 2 * d["D"] * itemsize
    return d["L"] * sum(blocks(p, block) * block for p in ctx) * per_tok


def step_bytes(d: Dict[str, int], ctx: Sequence[int], block: int,
               itemsize: int = 2) -> int:
    """Bytes one decode step has to move from or to HBM."""
    write = d["L"] * len(ctx) * 2 * d["D"] * itemsize
    return (weight_params(d) * itemsize + kv_read_bytes(d, ctx, block,
                                                        itemsize) + write)


def step_flops(d: Dict[str, int], ctx: Sequence[int]) -> int:
    """Model operations of one decode step (2 per multiply-add): the
    projections, MLP and head for every live token, and attention over
    ``p + 1`` positions (scores and weighted sum)."""
    attn = sum(4 * (p + 1) * d["D"] for p in ctx) * d["L"]
    return 2 * matmul_params(d) * len(ctx) + attn


def paged_attn_bytes(d: Dict[str, int], ctx: Sequence[int], block: int,
                     itemsize: int = 2) -> int:
    """One layer of the paged decode kernel: the live slots' KV blocks,
    and their queries and outputs."""
    kv = sum(blocks(p, block) * block for p in ctx) * 2 * d["D"] * itemsize
    return kv + 2 * len(ctx) * d["D"] * itemsize


def paged_attn_flops(d: Dict[str, int], ctx: Sequence[int]) -> int:
    """One layer of the paged decode kernel."""
    return sum(4 * (p + 1) * d["D"] for p in ctx)

