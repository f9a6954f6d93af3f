"""Three-term roofline from dry-run artifacts (TPU v5e targets).

  compute    = HLO_FLOPs_per_device / peak_FLOPs
  memory     = HLO_bytes_per_device / HBM_bw
  collective = wire_bytes_per_device / link_bw

FLOPs/bytes come from launch.hlo_cost (while-aware; XLA's cost_analysis
visits loop bodies once — see that module).  Wire bytes apply ring-model
factors per collective: all-gather/reduce-scatter (g-1)/g, all-reduce
2(g-1)/g, all-to-all (g-1)/g, collective-permute 1.

MODEL_FLOPS is the analytic useful-work count: 6*N_active*tokens for
training, 2*N_active*tokens for inference, with N_active excluding the
embedding table and counting only activated experts.  The ratio
MODEL_FLOPS/HLO_FLOPs surfaces remat recompute, causal-block waste and
TP head padding.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

from repro.configs import ModelConfig, ShapeSpec

# Published peaks of one TPU v5e chip (Google Cloud documentation,
# "TPU v5e"): 197 TFLOP/s in bf16, 819 GB/s of HBM bandwidth.
PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
LINK_BW = 50e9               # bytes/s / link (ICI)

# (peak flops/s, memory bytes/s) per device, keyed by what JAX reports:
# ``device_kind`` for accelerators, the platform name for the host CPU.
# The plan autotuner ranks schedule candidates with these before timing.
# A device that is not listed is an error, not a default.
DEVICE_PEAKS = {
    "TPU v5 lite": (PEAK_FLOPS, HBM_BW),
    # coarse host pair for CPU-side ranking, not a published peak
    "cpu": (100e9, 30e9),
}


def device_peaks(device=None) -> tuple[float, float]:
    """``(peak flops/s, bytes/s)`` of ``device`` (default: JAX's first
    device).  CPU devices resolve by platform, accelerators by
    ``device_kind``; an unlisted device raises."""
    import jax

    if device is None:
        device = jax.devices()[0]
    key = "cpu" if device.platform == "cpu" else device.device_kind
    return _peaks(key)


def _peaks(key: str) -> tuple[float, float]:
    try:
        return DEVICE_PEAKS[key]
    except KeyError:
        raise ValueError(
            f"no roofline peaks for device {key!r}; known: "
            f"{sorted(DEVICE_PEAKS)}") from None

_WIRE = {"all-gather": lambda g: (g - 1) / g,
         "reduce-scatter": lambda g: (g - 1) / g,
         "all-reduce": lambda g: 2 * (g - 1) / g,
         "all-to-all": lambda g: (g - 1) / g,
         "collective-permute": lambda g: 1.0}


def wire_bytes(collectives) -> float:
    """Per-device ring-model wire bytes from hlo_cost collective records."""
    total = 0.0
    for rec in collectives:
        if isinstance(rec, dict):
            op, b, g, t = (rec["op"], rec["payload_bytes"], rec["group"],
                           rec["trips"])
        else:
            op, b, g, t = rec
        if g <= 1:
            continue
        total += _WIRE[op](g) * b * t
    return total


# ---------------------------------------------------------------------------
# Analytic parameter counts (per layer kind), mirroring models/*
# ---------------------------------------------------------------------------
def _mixer_params(cfg: ModelConfig, kind: str, padded: bool) -> float:
    D, hd = cfg.d_model, cfg.head_dim
    H = cfg.n_heads_eff if padded else cfg.n_heads
    K = cfg.n_kv_eff if padded else cfg.n_kv_heads
    if kind in ("attn", "attn_local"):
        return D * H * hd + 2 * D * K * hd + H * hd * D
    if kind == "mla":
        ql, kl, rd = cfg.q_lora, cfg.kv_lora, cfg.rope_dim
        return (D * ql + ql * H * (hd + rd) + D * (kl + rd)
                + 2 * kl * H * hd + H * hd * D)
    if kind == "rglru":
        W = cfg.lru_width
        wb = W // cfg.n_heads            # block-diagonal gates
        return 2 * D * W + 2 * W * wb + W * D + cfg.conv_width * W
    if kind == "rwkv6":
        M = (cfg.rwkv_heads if padded else cfg.d_model // cfg.head_dim) * hd
        return 5 * D * M + D * 5 * 32 + D * 64 + 64 * M
    raise ValueError(kind)


def _ffn_params(cfg: ModelConfig, kind: str, active: bool) -> float:
    D = cfg.d_model
    if kind == "dense":
        return (3 if cfg.act in ("swiglu", "geglu") else 2) * D * cfg.d_ff
    if kind == "rwkv_cm":
        return 2 * D * cfg.d_ff
    # moe
    e = (cfg.top_k if active else cfg.n_experts)
    p = e * 3 * D * cfg.d_ff_expert + D * cfg.n_experts
    p += cfg.n_shared_experts * 3 * D * cfg.d_ff_expert
    return p


def param_count(cfg: ModelConfig, active: bool = False,
                padded: bool = False) -> float:
    """Non-embedding params (+ output head).  active=True -> MoE activated
    subset; padded=True -> include TP head padding (the HLO view)."""
    total = 0.0
    for mixers_t, ffn_kind, repeat in cfg.layer_plan():
        per = sum(_mixer_params(cfg, k, padded) for k in mixers_t)
        per += len(mixers_t) * _ffn_params(cfg, ffn_kind, active)
        total += per * repeat
    V = cfg.vocab_eff if padded else cfg.vocab_size
    D = cfg.d_model
    total += D * V                       # output head (tied or not: used)
    if cfg.enc_dec:                      # encoder stack + cross attention
        enc = cfg.n_enc_layers * (
            _mixer_params(cfg, "attn", padded)
            + _ffn_params(cfg, "dense", active))
        cross = cfg.n_layers * _mixer_params(cfg, "attn", padded)
        total += enc + cross
    if cfg.mtp:
        total += (_mixer_params(cfg, cfg.pattern[0], padded)
                  + _ffn_params(cfg, "dense", active) + 2 * D * D)
    return total


def model_flops(cfg: ModelConfig, shape: ShapeSpec) -> float:
    """Global analytic useful FLOPs for one step of this cell."""
    N = param_count(cfg, active=True, padded=False)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        if cfg.enc_dec:
            tokens *= 2                  # encoder + decoder streams
        return 6.0 * N * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * N * tokens * (2 if cfg.enc_dec else 1)
    return 2.0 * N * shape.global_batch  # decode: one token per row


def attn_flops(cfg: ModelConfig, shape: ShapeSpec) -> float:
    """Causal-optimal attention score+value FLOPs (not in 6ND)."""
    B, S = shape.global_batch, shape.seq_len
    H, hd = cfg.n_heads, cfg.head_dim
    n_attn = sum(k in ("attn", "attn_local", "mla")
                 for k in cfg.pattern) / len(cfg.pattern) * cfg.n_layers
    if shape.kind == "decode":
        eff_s = S if cfg.window is None else min(cfg.window, S)
        per_tok = 2 * 2 * H * hd * eff_s   # read the visible cache
        return n_attn * B * per_tok
    eff = S * S / 2 if cfg.window is None else S * min(cfg.window, S)
    fl = n_attn * B * 2 * 2 * H * hd * eff
    if shape.kind == "train":
        fl *= 3                          # fwd + bwd(2x)
    return fl


@dataclasses.dataclass
class PlanRoofline:
    """Two-term roofline for one compiled-plan candidate (single host,
    no collectives): predicted seconds and predicted cells/sec — the
    quantity the autotuner ranks schedule candidates by before timing."""
    compute_s: float
    memory_s: float
    cells: float

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s)

    @property
    def cells_per_s(self) -> float:
        return self.cells / max(self.bound_s, 1e-12)

    @property
    def dominant(self) -> str:
        return "compute" if self.compute_s >= self.memory_s else "memory"


def plan_roofline(cost, cells: float, *, backend: str = None,
                  trips: float = 1.0) -> PlanRoofline:
    """Roofline terms for one plan candidate from a ``hlo_cost.Cost``.

    ``backend`` names a :data:`DEVICE_PEAKS` entry (``"cpu"`` or a
    ``device_kind``); ``None`` takes JAX's first device.

    ``cost`` usually comes from :func:`hlo_cost.analyze_plan` over
    *lowered* (un-compiled) HLO, where while-loop trip counts are not
    yet annotated — the caller passes the analytic ``trips`` of the
    dominant fill loop (e.g. ``ceil((Q + R) / strip)`` wavefront steps)
    and both terms scale by it.  Elementwise flops dominate DP fills
    (there are no dots), so the compute term uses ``flops +
    ewise_flops``.
    """
    peak, bw = device_peaks() if backend is None else _peaks(backend)
    return PlanRoofline(
        compute_s=(cost.flops + cost.ewise_flops) * trips / peak,
        memory_s=cost.bytes * trips / bw,
        cells=cells)


@dataclasses.dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float
    hlo_flops_global: float
    useful_ratio: float

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)


def from_record(rec: Dict, cfg: ModelConfig, shape: ShapeSpec) -> Roofline:
    """Build roofline terms from one dryrun JSONL record."""
    n_dev = 512 if rec["mesh"] == "2x16x16" else 256
    hc = rec["hlo_cost"]
    fl_dev = hc["flops_per_device"]
    mf = model_flops(cfg, shape) + attn_flops(cfg, shape)
    return Roofline(
        compute_s=fl_dev / PEAK_FLOPS,
        memory_s=hc["bytes_per_device"] / HBM_BW,
        collective_s=wire_bytes(hc["collectives"]) / LINK_BW,
        model_flops=mf,
        hlo_flops_global=fl_dev * n_dev,
        useful_ratio=mf / max(fl_dev * n_dev, 1.0),
    )
