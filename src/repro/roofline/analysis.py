"""Roofline analysis from compiled dry-run artifacts (assignment §ROOFLINE).

Three terms per (arch × shape × mesh), all in seconds:

    compute    = HLO_FLOPs_per_device / PEAK_FLOPS
    memory     = HLO_bytes_per_device / HBM_BW
    collective = Σ_ops ring_time(op_kind, bytes, group_size) over the
                 **optimized post-SPMD HLO** (collective bytes are not in
                 cost_analysis; we parse ``compiled.as_text()``)

Hardware model: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link
ICI. Ring-collective cost model per op kind (n = group size):

    all-gather      bytes_out × (n-1)/n / BW
    reduce-scatter  bytes_in  × (n-1)/n / BW
    all-reduce      2 × bytes × (n-1)/n / BW
    all-to-all      bytes × (n-1)/n / BW
    collective-permute  bytes / BW
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

PEAK_FLOPS = 197e12        # bf16 / chip
HBM_BW = 819e9             # bytes/s / chip
ICI_BW = 50e9              # bytes/s / link (effective per-chip per-collective)

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "s4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "token": 0,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# `%x = bf16[128,1024]{1,0} all-gather(...)`  (also tuple results)
_OP_RE = re.compile(
    r"=\s*(?P<shape>\((?:[^()]*)\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s*"
    r"(?P<op>all-reduce-start|all-gather-start|reduce-scatter|all-to-all|"
    r"collective-permute-start|all-reduce|all-gather|collective-permute)\b"
    r"(?P<rest>.*)")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([0-9, ]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(rest: str) -> int:
    m = _GROUPS_IOTA_RE.search(rest)
    if m:  # iota format [groups, group_size]
        return int(m.group(2))
    m = _GROUPS_RE.search(rest)
    if m:
        return len(m.group(1).split(","))
    return 2


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_kind: Dict[str, int]
    seconds_by_kind: Dict[str, float]
    count: int

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())

    @property
    def total_seconds(self) -> float:
        return sum(self.seconds_by_kind.values())


def parse_collectives(hlo_text: str, ici_bw: float = ICI_BW) -> CollectiveStats:
    bytes_by: Dict[str, int] = {}
    secs_by: Dict[str, float] = {}
    count = 0
    for line in hlo_text.splitlines():
        if "fusion" in line and all(c not in line for c in _COLLECTIVES):
            continue
        m = _OP_RE.search(line)
        if not m:
            continue
        op = m.group("op").replace("-start", "")
        nbytes = _shape_bytes(m.group("shape"))
        n = _group_size(m.group("rest"))
        if n <= 1:
            continue
        frac = (n - 1) / n
        if op == "all-reduce":
            secs = 2 * nbytes * frac / ici_bw
        elif op == "collective-permute":
            secs = nbytes / ici_bw
        else:  # all-gather (result), reduce-scatter (operand≈result parsed)
            secs = nbytes * frac / ici_bw
        bytes_by[op] = bytes_by.get(op, 0) + nbytes
        secs_by[op] = secs_by.get(op, 0.0) + secs
        count += 1
    return CollectiveStats(bytes_by, secs_by, count)


# ----------------------------------------------------------------------------
@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_device: float
    bytes_per_device: float
    collective: CollectiveStats
    model_flops: float            # 6·N_active·D (global)
    memory_per_device: Dict[str, float]
    step_kind: str
    bytes_by_opcode: Dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / PEAK_FLOPS

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / HBM_BW

    @property
    def collective_s(self) -> float:
        return self.collective.total_seconds

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        total_hlo = self.flops_per_device * self.chips
        return self.model_flops / total_hlo if total_hlo else 0.0

    @property
    def roofline_fraction(self) -> float:
        """useful-compute time / achievable step time (max of the terms):
        how close the step is to the compute roofline for its useful FLOPs."""
        useful_s = (self.model_flops / self.chips) / PEAK_FLOPS
        bound = max(self.compute_s, self.memory_s, self.collective_s)
        return useful_s / bound if bound else 0.0

    def to_dict(self) -> Dict[str, Any]:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips, "step_kind": self.step_kind,
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "collective_bytes": self.collective.bytes_by_kind,
            "collective_count": self.collective.count,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
            "memory_per_device": self.memory_per_device,
            "bytes_by_opcode": self.bytes_by_opcode,
        }


def analyze(compiled, *, arch: str, shape: str, mesh_name: str, chips: int,
            model_flops: float, step_kind: str, seq_dims=None) -> Roofline:
    """Derive the three terms from the compiled artifact.

    ``cost_analysis()`` counts while-loop bodies once, so scanned programs
    are undercounted by their trip counts; we use the static HLO analyzer
    (``hlo_stats``) which multiplies through the loop nest. XLA's own
    numbers are preserved in ``memory_per_device['xla_cost_*']``."""
    from . import hlo_stats
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    stats = hlo_stats.analyze_module(hlo, ici_bw=ICI_BW, seq_dims=seq_dims)
    flops = stats.flops
    nbytes = stats.bytes_accessed
    coll = CollectiveStats(
        {k: int(v) for k, v in stats.collective_bytes.items()},
        {"total": stats.collective_seconds}, stats.collective_count)
    mem: Dict[str, float] = {}
    try:
        ma = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            v = getattr(ma, attr, None)
            if v is not None:
                mem[attr] = float(v)
    except Exception:
        pass
    mem["xla_cost_flops_loop_bodies_once"] = float(cost.get("flops", 0.0))
    mem["xla_cost_bytes_loop_bodies_once"] = float(
        cost.get("bytes accessed", 0.0))
    # counterfactual: memory term with attention-score traffic kept in VMEM
    # (what the Pallas flash kernel — the TPU deploy path — achieves)
    mem["bytes_scores_class"] = float(stats.bytes_scores_class)
    mem["memory_s_flash_equiv"] = float(
        (stats.bytes_accessed - stats.bytes_scores_class) / HBM_BW)
    return Roofline(arch=arch, shape=shape, mesh=mesh_name, chips=chips,
                    flops_per_device=flops, bytes_per_device=nbytes,
                    collective=coll, model_flops=model_flops,
                    memory_per_device=mem, step_kind=step_kind,
                    bytes_by_opcode=dict(stats.bytes_by_opcode))


def model_flops_for(cfg, shape_name: str, seq: int, global_batch: int,
                    step_kind: str) -> float:
    """Useful model FLOPs: 6·N_active·D plus the attention term
    (PaLM-appendix-style MFU accounting — at 32k+ context the S² attention
    FLOPs dominate the parameter FLOPs and must be credited)."""
    n_active = cfg.active_param_count()
    h, hd = cfg.n_heads, cfg.resolved_head_dim

    def attn_fwd_per_seq(s_ctx: int) -> float:
        """QKᵀ + PV over a causal context (½ the pairs count)."""
        if cfg.is_attention_free or not h:
            return 0.0
        l_attn = cfg.n_layers
        eff = s_ctx
        if cfg.family == "hybrid":
            pat = cfg.hybrid.pattern or ("attn",)
            l_attn = cfg.n_layers * sum(1 for p in pat if p == "attn") / len(pat)
            eff = min(s_ctx, 2 * cfg.hybrid.window)  # local window
        per_layer = 2.0 * s_ctx * eff * h * hd  # causal ½ × (2 matmuls × 2)
        enc = 0.0
        if cfg.family == "encdec":
            t = cfg.encdec.n_frames
            enc = cfg.encdec.n_enc_layers * 4.0 * t * t * h * hd  # bidirectional
        return l_attn * per_layer + enc

    if step_kind == "train":
        return (6.0 * n_active * seq +
                3.0 * attn_fwd_per_seq(seq)) * global_batch
    if step_kind == "prefill":
        return (2.0 * n_active * seq + attn_fwd_per_seq(seq)) * global_batch
    # decode: one token against an s_ctx-deep cache → 4·S·H·Dh per layer
    l_attn = cfg.n_layers
    eff = seq
    if cfg.family == "hybrid":
        pat = cfg.hybrid.pattern or ("attn",)
        l_attn = cfg.n_layers * sum(1 for p in pat if p == "attn") / len(pat)
        eff = min(seq, cfg.hybrid.window)
    attn_dec = 0.0 if (cfg.is_attention_free or not h) else \
        l_attn * 4.0 * eff * h * hd
    return (2.0 * n_active + attn_dec) * global_batch
