"""Compiled-HLO analysis: collective wire bytes, op census, roofline terms.

``cost_analysis()`` gives per-device FLOPs and HBM bytes but not
collective traffic; we parse the optimized HLO text and sum operand sizes
of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute, converted to ring-algorithm wire bytes per device.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLL_RE = re.compile(
    r"=\s+(?:\()?([a-z0-9]+)\[([0-9,]*)\][^\s]*\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")

_TUPLE_COLL_RE = re.compile(
    r"=\s+\(([^)]*)\)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")

_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_SRC_TGT_RE = re.compile(r"source_target_pairs=\{")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


@dataclass
class CollectiveStats:
    counts: Dict[str, int] = field(default_factory=dict)
    result_bytes: Dict[str, int] = field(default_factory=dict)
    wire_bytes: Dict[str, float] = field(default_factory=dict)

    @property
    def total_wire_bytes(self) -> float:
        return sum(self.wire_bytes.values())

    def row(self) -> Dict[str, float]:
        return {"counts": dict(self.counts),
                "result_bytes": dict(self.result_bytes),
                "wire_bytes": {k: round(v) for k, v in
                               self.wire_bytes.items()},
                "total_wire_bytes": round(self.total_wire_bytes)}


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS2_RE.search(line)
    if m:  # replica_groups=[n_groups,group_size]
        return int(m.group(2))
    return default


def collective_stats(hlo_text: str, default_group: int = 1
                     ) -> CollectiveStats:
    """Sum per-device ring wire bytes of every collective in the module."""
    st = CollectiveStats()
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        tuple_elems: List[Tuple[str, str]] = []
        if not m:
            mt = _TUPLE_COLL_RE.search(line)
            if not mt:
                continue
            op = mt.group(2)
            for em in re.finditer(r"([a-z0-9]+)\[([0-9,]*)\]",
                                  mt.group(1)):
                tuple_elems.append((em.group(1), em.group(2)))
        else:
            op = m.group(3)
            tuple_elems = [(m.group(1), m.group(2))]
        rbytes = sum(_shape_bytes(d, s) for d, s in tuple_elems)
        g = _group_size(line, default_group)
        if op == "all-reduce":
            wire = 2.0 * (g - 1) / max(g, 1) * rbytes
        elif op == "all-gather":
            wire = (g - 1) / max(g, 1) * rbytes        # result = gathered
        elif op == "reduce-scatter":
            wire = (g - 1) * rbytes                    # result = shard
        elif op == "all-to-all":
            wire = (g - 1) / max(g, 1) * rbytes
        else:                                          # collective-permute
            wire = float(rbytes)
        st.counts[op] = st.counts.get(op, 0) + 1
        st.result_bytes[op] = st.result_bytes.get(op, 0) + rbytes
        st.wire_bytes[op] = st.wire_bytes.get(op, 0.0) + wire
    return st


def op_census(hlo_text: str, ops=("fusion", "dot", "convolution",
                                  "dynamic-slice", "dynamic-update-slice",
                                  "transpose", "copy", "while")) -> Dict[str, int]:
    out = {}
    for op in ops:
        out[op] = len(re.findall(rf"\s{op}(?:\.\d+)?\(", hlo_text))
    return out


_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%(\S+)\s*=\s*([a-z0-9]+)\[([0-9,]*)\]"
    r"(?:\{([^}]*)\})?\s+([a-z][a-z0-9-]*)\(")
_COMP_RE = re.compile(r"^\s*(?:ENTRY\s+)?%(\S+)\s*\(.*\{\s*$")
_ROOT_OP_RE = re.compile(r"^\s*ROOT\s+%\S+\s*=\s*\S+\s+([a-z][a-z0-9-]*)\(")
# ops that name, forward or reinterpret a buffer without writing it
_NO_WRITE = ("parameter", "get-tuple-element", "bitcast", "tuple", "while",
             "call", "conditional", "custom-call", "copy-start",
             "opt-barrier")
# in-place updates: their result aliases their first operand
_IN_PLACE = ("scatter", "dynamic-update-slice")


@dataclass
class PoolCensus:
    """What a compiled program does to a stacked KV pool (see
    :func:`pool_census`): op names, and the pool-shaped layouts."""
    copies: List[str] = field(default_factory=list)
    per_layer: List[str] = field(default_factory=list)
    layouts: List[str] = field(default_factory=list)

    def row(self) -> Dict[str, object]:
        return {"whole_pool_copies": len(self.copies),
                "per_layer_pool_ops": len(self.per_layer),
                "pool_layouts": sorted(set(self.layouts))}


def hlo_dtype(dtype) -> str:
    """HLO's name of a numpy / JAX dtype: ``bf16``, ``f32``, ``s8``,
    ``f8e4m3fn``, ``pred``, ..."""
    name = np.dtype(dtype).name
    if name == "bool":
        return "pred"
    if name.startswith("float8_"):
        return "f8" + name[len("float8_"):]
    for prefix, short in (("bfloat", "bf"), ("float", "f"), ("uint", "u"),
                          ("int", "s")):
        if name.startswith(prefix):
            return short + name[len(prefix):]
    raise ValueError(f"no HLO name for dtype {name}")


def pool_census(hlo_text: str, leaf) -> PoolCensus:
    """Count the ops of compiled HLO text that move a stacked pool leaf.

    ``leaf`` is the leaf, or anything with its (L, ...) ``shape`` and its
    ``dtype``.  Only ops that run as their own kernel are counted:
    instructions inside fused computations are parts of their fusion.

    * ``copies``: ops writing a value of the whole leaf's size (its dims
      in any order, so a transposed relayout counts) that is neither an
      in-place update (scatter, dynamic-update-slice, or a fusion rooted
      in one) nor a view (bitcast, tuple plumbing, a custom call).
    * ``per_layer``: ops producing one layer of it, ``pool_shape[1:]``
      with or without a leading 1, in any order: a slice cut from the
      stack or a relayout of one.
    * ``layouts``: the minor-to-major layout of every value of exactly
      ``pool_shape``, one entry per value.
    """
    pool_shape = [int(d) for d in leaf.shape]
    dtype = hlo_dtype(leaf.dtype)
    full, layer = sorted(pool_shape), sorted(pool_shape[1:])
    layer1 = sorted([1] + layer)
    fused, roots, comp = set(), {}, None
    lines = hlo_text.splitlines()
    for line in lines:
        m = _COMP_RE.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _ROOT_OP_RE.match(line)
        if m and comp is not None:
            roots[comp] = m.group(1)
        fused.update(re.findall(r"calls=%([^\s,)]+)", line))
    out, comp = PoolCensus(), None
    for line in lines:
        m = _COMP_RE.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTR_RE.match(line)
        if not m or comp in fused or m.group(2) != dtype:
            continue
        name, _, dims, layout, op = m.groups()
        shape = [int(d) for d in dims.split(",") if d]
        dims = sorted(shape)
        if dims == full:
            if shape == pool_shape:
                out.layouts.append((layout or "").split(":")[0])
            callee = re.search(r"calls=%([^\s,)]+)", line)
            in_place = op in _IN_PLACE or (
                op == "fusion" and callee is not None
                and roots.get(callee.group(1)) in _IN_PLACE)
            if op not in _NO_WRITE and not in_place:
                out.copies.append(name)
        elif dims in (layer, layer1) and op not in _NO_WRITE:
            out.per_layer.append(name)
    return out


@dataclass
class Roofline:
    """Three-term roofline (per device) in seconds."""
    flops: float
    hbm_bytes: float
    wire_bytes: float
    peak_flops: float
    hbm_bw: float
    link_bw: float

    @property
    def t_compute(self) -> float:
        return self.flops / self.peak_flops

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / self.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.wire_bytes / self.link_bw

    @property
    def bottleneck(self) -> str:
        t = {"compute": self.t_compute, "memory": self.t_memory,
             "collective": self.t_collective}
        return max(t, key=t.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    def row(self) -> Dict[str, float]:
        return {
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "wire_bytes": self.wire_bytes,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
        }
