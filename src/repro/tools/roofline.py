"""Roofline analysis from the compiled dry-run artifact (deliverable g).

Three terms per (arch, shape, mesh), in seconds:

  compute    = HLO_FLOPs / (chips * peak_FLOP/s)
  memory     = HLO_bytes / (chips * HBM_bw)
  collective = collective_bytes / (chips * link_bw)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()``; collective
bytes are NOT in cost_analysis, so we parse the optimized HLO text and sum
operand sizes of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute ops. MODEL_FLOPS = 6 N D (dense) or 6 N_active D (MoE)
anchors the "useful compute" ratio.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field


@dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float       # FLOP/s per chip
    int8_ops: float         # OP/s per chip
    hbm_bytes_per_s: float  # HBM bandwidth per chip
    hbm_bytes: float        # HBM capacity per chip
    ici_bytes_per_s: float  # chip-to-chip interconnect per chip


#: Published per-chip peaks keyed by ``jax.Device.device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" (system architecture):
#: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI.
PEAKS = {
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes_per_s=819e9,
        hbm_bytes=16e9, ici_bytes_per_s=1600e9 / 8,
    ),
}


def peaks(device_kind: str) -> ChipPeaks:
    """Peaks of one chip of ``device_kind``; a kind not in ``PEAKS`` is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

# e.g. "bf16[16,1024,128]{2,1,0} all-gather(...)" — capture result type + op
_OP_RE = re.compile(
    r"=\s*(?:\([^)]*\)\s*)?([a-z0-9]+)\[([0-9,]*)\][^ ]*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
# tuple-result collectives: "= (f32[...], f32[...]) all-reduce-start(...)"
_TUPLE_RE = re.compile(
    r"=\s*\(([^)]*)\)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


@dataclass
class CollectiveStats:
    bytes_by_kind: dict = field(default_factory=dict)
    count_by_kind: dict = field(default_factory=dict)

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())

    def add(self, kind: str, nbytes: int) -> None:
        self.bytes_by_kind[kind] = self.bytes_by_kind.get(kind, 0) + nbytes
        self.count_by_kind[kind] = self.count_by_kind.get(kind, 0) + 1


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum result sizes of every collective op in the optimized HLO."""
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if not any(c in stripped for c in _COLLECTIVES):
            continue
        m = _OP_RE.search(stripped)
        if m:
            dtype, dims, kind = m.groups()
            stats.add(kind, _shape_bytes(dtype, dims))
            continue
        m = _TUPLE_RE.search(stripped)
        if m:
            inner, kind = m.groups()
            total = sum(
                _shape_bytes(d, s) for d, s in _SHAPE_RE.findall(inner)
            )
            # tuple results hold (operand, result) for -start ops: halve to
            # avoid double counting the aliased input buffer
            stats.add(kind, total // 2 if "-start" in stripped else total)
    return stats


@dataclass
class Roofline:
    flops: float              # whole-program HLO flops (all chips)
    hbm_bytes: float          # whole-program HLO bytes accessed
    collective_bytes: float   # whole-program bytes moved by collectives
    chips: int
    model_flops: float        # 6*N(_active)*D useful flops
    device_kind: str          # key into PEAKS

    @property
    def compute_s(self) -> float:
        return self.flops / (self.chips * peaks(self.device_kind).bf16_flops)

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / (
            self.chips * peaks(self.device_kind).hbm_bytes_per_s)

    @property
    def collective_s(self) -> float:
        return self.collective_bytes / (
            self.chips * peaks(self.device_kind).ici_bytes_per_s)

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def useful_ratio(self) -> float:
        return self.model_flops / self.flops if self.flops else 0.0

    def as_dict(self) -> dict:
        return {
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "collective_bytes": self.collective_bytes,
            "chips": self.chips,
            "model_flops": self.model_flops,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "useful_ratio": self.useful_ratio,
        }


def model_flops_estimate(cfg, tokens: int, kind: str) -> float:
    """6*N*D for training, 2*N*D for inference (N = active params)."""
    n_active = cfg.active_params()
    per_token = 6.0 if kind == "train" else 2.0
    return per_token * n_active * tokens


def roofline_from_costs(per_device: dict, cfg, shape_spec, chips: int,
                        device_kind: str) -> Roofline:
    """Build a Roofline from per-device cost dict (composite or direct)."""
    tokens = shape_spec.global_batch * (
        shape_spec.seq_len if shape_spec.kind != "decode" else 1
    )
    return Roofline(
        flops=per_device["flops"] * chips,
        hbm_bytes=per_device["bytes"] * chips,
        collective_bytes=per_device["collective_bytes"] * chips,
        chips=chips,
        model_flops=model_flops_estimate(cfg, tokens, shape_spec.kind),
        device_kind=device_kind,
    )


def roofline_from_compiled(compiled, cfg, shape_spec, chips: int,
                           device_kind: str) -> Roofline:
    cost = compiled.cost_analysis()
    # jax 0.8: cost_analysis() returns a dict (or list of one dict)
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    # cost_analysis reports PER-DEVICE quantities (the compiled module is the
    # per-device SPMD program — calibrated in EXPERIMENTS.md §Dry-run); the
    # roofline terms divide by chips, so scale back to whole-program numbers.
    flops = float(cost.get("flops", 0.0)) * chips
    hbm = float(cost.get("bytes accessed", 0.0)) * chips
    # collective shapes in the partitioned HLO are per-device shards as well:
    # total_bytes is per-device traffic; whole-program = x chips.
    stats = parse_collectives(compiled.as_text())
    tokens = shape_spec.global_batch * (
        shape_spec.seq_len if shape_spec.kind != "decode" else 1
    )
    return Roofline(
        flops=flops,
        hbm_bytes=hbm,
        collective_bytes=float(stats.total_bytes) * chips,
        chips=chips,
        model_flops=model_flops_estimate(cfg, tokens, shape_spec.kind),
        device_kind=device_kind,
    )
