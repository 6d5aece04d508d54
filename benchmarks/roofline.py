"""Roofline analysis (deliverable g): three terms per (arch x mesh) from the
dry-run artifacts, dominant bottleneck, MODEL_FLOPS ratio.

    compute    = HLO_FLOPs_per_device / peak_FLOP/s          (bf16)
    memory     = HLO_bytes_per_device / HBM_bw
    collective = collective_bytes_per_device / link_bw

Peaks come from ``CHIP_PEAKS``, keyed by the record's ``device_kind``; a
device that is not in the table raises instead of borrowing another
chip's numbers.

HLO quantities are the loop-corrected per-device values (launch/cost.py).
Caveats recorded in EXPERIMENTS.md: 'bytes accessed' is an upper bound on
HBM traffic (XLA counts every operand access; VMEM reuse is not modeled),
and collective bytes assume a single ICI link per hop.
"""
from __future__ import annotations

import glob
import json
import os

from repro import configs
from repro.models.config import ModelConfig

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                            "dryrun")


# Published per-chip peaks, keyed by jax's ``device_kind``.  Source: Google
# Cloud, "TPU v5e" (system architecture): 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB HBM at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect over four
# links (50 GB/s per link).
CHIP_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bw": 819e9,
                    "ici_link_bw": 50e9},
}


def chip_peaks(device_kind: str) -> dict:
    """The peak table row of ``device_kind``; raises for a device with no
    published peaks here (a CPU among them)."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peak table entry for device {device_kind!r} "
                         f"(known: {sorted(CHIP_PEAKS)})") from None


# ---------------------------------------------------------------------------
# MODEL_FLOPS: 6 N D (dense train) / 6 N_active D (MoE train) / 2 N D decode
# ---------------------------------------------------------------------------

def active_params(cfg: ModelConfig) -> int:
    """Parameters touched per token (embedding lookup excluded, unembed
    matmul included — it executes as a matmul)."""
    d, dh = cfg.d_model, cfg.resolved_head_dim
    total = 0
    kinds = cfg.block_kinds()
    for i, kind in enumerate(kinds):
        if kind in ("attn", "swa", "local", "enc_attn"):
            if cfg.attn_kind == "mla":
                qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
                q = (cfg.q_lora_rank * (d + cfg.n_heads * qk)
                     if cfg.q_lora_rank else d * cfg.n_heads * qk)
                kv = d * (cfg.kv_lora_rank + cfg.qk_rope_head_dim) \
                    + cfg.kv_lora_rank * cfg.n_heads * (
                        cfg.qk_nope_head_dim + cfg.v_head_dim)
                o = cfg.n_heads * cfg.v_head_dim * d
                total += q + kv + o
            else:
                total += d * dh * (cfg.n_heads + 2 * cfg.n_kv_heads) \
                    + cfg.n_heads * dh * d
        elif kind == "ssd":
            d_inner = cfg.ssm_expand * d
            nheads = d_inner // cfg.ssm_headdim
            gn = cfg.ssm_ngroups * cfg.ssm_state
            total += d * (2 * d_inner + 2 * gn + nheads) + d_inner * d
        elif kind == "rglru":
            w = cfg.lru_width or d
            total += 2 * d * w + 2 * w * w + w * d
        # ffn
        if kind == "ssd" and cfg.ffn_kind == "none":
            continue
        n_mats = 3 if cfg.ffn_kind in ("swiglu", "geglu") else 2
        if cfg.layer_is_moe(i):
            active_e = cfg.moe_top_k + cfg.moe_shared_experts
            total += cfg.moe_num_experts * d \
                + active_e * n_mats * d * cfg.expert_d_ff
        else:
            total += n_mats * d * cfg.d_ff
    if cfg.is_enc_dec:
        # encoder layers + decoder cross-attention
        enc = cfg.encoder_layers * (d * dh * (cfg.n_heads + 2 * cfg.n_kv_heads)
                                    + cfg.n_heads * dh * d
                                    + 2 * d * cfg.d_ff)
        cross = cfg.n_layers * (d * dh * (cfg.n_heads + 2 * cfg.n_kv_heads)
                                + cfg.n_heads * dh * d)
        total += enc + cross
    total += d * cfg.padded_vocab           # unembed matmul
    return total


def model_flops(arch: str, shape_name: str) -> float:
    shape = configs.get_shape(shape_name)
    cfg = (configs.long_context_config(arch) if shape_name == "long_500k"
           else configs.get_config(arch))
    n_act = active_params(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_act * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_act * tokens
    return 2.0 * n_act * shape.global_batch        # decode: 1 token/request


def roofline_row(record: dict) -> dict:
    n_dev = record["devices"]
    flops = record.get("flops_per_device_corrected",
                       record["flops_per_device"])
    byts = record.get("bytes_per_device_corrected",
                      record["bytes_accessed_per_device"])
    coll = record.get("collective_bytes_corrected",
                      record["collective_bytes_per_device"]["total"])
    peaks = chip_peaks(record["device_kind"])
    t_compute = flops / peaks["bf16_flops"]
    t_memory = byts / peaks["hbm_bw"]
    t_coll = coll / peaks["ici_link_bw"]
    terms = {"compute": t_compute, "memory": t_memory,
             "collective": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops(record["arch"], record["shape"])
    mf_dev = mf / n_dev
    return {
        "arch": record["arch"],
        "shape": record["shape"],
        "devices": n_dev,
        "compute_s": t_compute,
        "memory_s": t_memory,
        "collective_s": t_coll,
        "dominant": dominant,
        "model_flops_per_device": mf_dev,
        "useful_flops_ratio": mf_dev / flops if flops > 0 else 0.0,
        "hbm_gb_per_device": record["memory_analysis"].get(
            "argument_bytes", 0) / 1e9,
    }


# ---------------------------------------------------------------------------
# Fused OTA round-step kernel (DESIGN.md §Kernels): analytic roofline of one
# ota_round_step launch per uplink dtype, vs the unfused four-op chain.
# ---------------------------------------------------------------------------

def ota_round_step_roofline(device_kind: str, n: int = 10,
                            d: int = 814_090) -> list:
    """Compute/memory terms of the fused round-step kernel at [N, D] on
    the chip ``device_kind`` names.

    Traffic of one fused launch: the [N, D] uplink at wire precision in,
    z + params in and params out at f32 — the unfused chain adds a ghat
    f32 write + read between the aggregate and step launches.  FLOPs:
    dequantize + precode-weight + accumulate over N (~3ND) plus the
    noise-add and SGD step (~4D).  At the paper's scale the arithmetic
    intensity is ~0.7–1.5 FLOPs/byte — far below the compute/memory
    ridge — so the kernel is memory-bound for every wire dtype and the
    fusion's saved ghat round-trip — and a narrower uplink — convert
    directly into wall time.
    """
    peaks = chip_peaks(device_kind)
    rows = []
    from repro.kernels.ops import UPLINK_WIRE_BYTES

    for ud, wire in UPLINK_WIRE_BYTES.items():
        fused_bytes = n * d * wire + 3 * d * 4
        unfused_bytes = fused_bytes + 2 * d * 4
        flops = 3.0 * n * d + 4.0 * d
        t_compute = flops / peaks["bf16_flops"]
        t_memory = fused_bytes / peaks["hbm_bw"]
        rows.append({
            "kernel": "ota_round_step", "uplink_dtype": ud,
            "n": n, "d": d,
            "compute_s": t_compute,
            "memory_s": t_memory,
            "unfused_memory_s": unfused_bytes / peaks["hbm_bw"],
            "dominant": "compute" if t_compute > t_memory else "memory",
            "flops_per_byte": flops / fused_bytes,
            "fused_bytes_mb": fused_bytes / 1e6,
            "unfused_bytes_mb": unfused_bytes / 1e6,
        })
    return rows


def load_records(pattern: str = "*_pod.json") -> list:
    rows = []
    for path in sorted(glob.glob(os.path.join(ARTIFACT_DIR, pattern))):
        with open(path) as f:
            rows.append(json.load(f))
    return rows


def run() -> list:
    return [roofline_row(r) for r in load_records()]


if __name__ == "__main__":
    import jax

    for row in run():
        print(row)
    for row in ota_round_step_roofline(jax.devices()[0].device_kind):
        print(row)
