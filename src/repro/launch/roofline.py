"""Roofline analysis over the dry-run artifacts.

Per (arch x shape) cell on the single-pod mesh, derives the three-term
roofline from the loop-corrected HLO cost analysis:

  compute    = HLO_FLOPs_global  / (chips * PEAK_FLOPS)
  memory     = HLO_bytes_global  / (chips * HBM_BW)
  collective = wire_bytes_global / (chips * LINK_BW)

where HLO_FLOPs/bytes come from ``hlo_analysis`` (per-device, x chips for
global) and collective wire bytes apply ring-model factors per kind:
  all-gather / reduce-scatter: (D-1)/D * payload
  all-reduce:               2 * (D-1)/D * payload
  all-to-all:                (D-1)/D * payload
  collective-permute:         payload
(Payload = result-shape bytes already per device; D inferred from the
op's use of the mesh is approximated by the TP width since TP collectives
dominate — documented approximation.)

Also reports MODEL_FLOPS = 6*N*D_tokens (train) / 2*N_active*D (decode/
prefill), the useful-compute ratio MODEL/HLO, the dominant term, and the
roofline fraction = MODEL_FLOPS_time / max(term).

Usage: ``python -m repro.launch.roofline [--mesh single] [--markdown]``
"""
from __future__ import annotations

import argparse
import glob
import json
import os

from repro.hw import TPU_V5E, peaks

# per-chip peaks come from the shared hardware table (repro.hw) —
# the single source of truth shared with the registry's cost model
_HW = peaks(TPU_V5E)
PEAK_FLOPS = _HW.mxu_flops   # bf16 MXU / chip (v5e)
VPU_FLOPS = _HW.vpu_flops    # ~elementwise ops/s / chip (8x128 VPU, est.)
HBM_BW = _HW.hbm_bw          # B/s / chip
LINK_BW = _HW.link_bw        # B/s / link (ICI)

HERE = os.path.dirname(__file__)
DRYRUN_DIR = os.path.join(HERE, "..", "..", "..", "experiments", "dryrun")

# total params and active params per arch (from eval_shape; active =
# dense-equivalent params touched per token for MoE)
PARAMS = {
    "starcoder2-3b": (3.030e9, 3.030e9),
    "smollm-135m": (0.135e9, 0.135e9),
    "llama3-405b": (405.9e9, 405.9e9),
    "gemma3-4b": (3.880e9, 3.880e9),
    "recurrentgemma-9b": (9.396e9, 9.396e9),
    "chameleon-34b": (34.29e9, 34.29e9),
    "deepseek-v2-lite-16b": (15.71e9, 2.66e9),
    "kimi-k2-1t-a32b": (1028.3e9, 32.4e9),
    "mamba2-370m": (0.368e9, 0.368e9),
    "whisper-large-v3": (1.535e9, 1.535e9),
}


def model_flops(arch: str, kind: str, seq: int, batch: int,
                dec_len: int = 448) -> float:
    n_total, n_active = PARAMS[arch]
    if kind == "train":
        tokens = seq * batch
        if arch == "whisper-large-v3":
            tokens = (seq + min(dec_len, seq)) * batch
        return 6.0 * n_active * tokens
    if kind == "prefill":
        tokens = seq * batch
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * batch


def coll_seconds(coll: dict, chips: int, tp: int = 16) -> float:
    """Ring-model collective time per device (seconds)."""
    f = (tp - 1) / tp
    t = 0.0
    t += coll.get("all-gather", 0) * f
    t += coll.get("reduce-scatter", 0) * f
    t += coll.get("all-reduce", 0) * 2 * f
    t += coll.get("all-to-all", 0) * f
    t += coll.get("collective-permute", 0)
    return t / LINK_BW


def analyze(rec: dict) -> dict:
    chips = rec["chips"]
    hc = rec["hlo_cost"]
    flops_dev = hc["flops_per_device"]
    dot_dev = hc.get("dot_flops_per_device", flops_dev)
    # compute term: MXU work at MXU peak + elementwise work at VPU rate
    compute_s = dot_dev / PEAK_FLOPS + (flops_dev - dot_dev) / VPU_FLOPS
    # memory term: geometric mean of the fusion-blind upper bound
    # (operands+results) and the fusion-perfect lower bound (results only)
    b_hi = hc["bytes_per_device"]
    b_lo = hc.get("bytes_lo_per_device", b_hi)
    bytes_dev = (b_hi * max(b_lo, 1)) ** 0.5
    memory_s = bytes_dev / HBM_BW
    coll_s = coll_seconds(hc["collective_bytes_per_device"], chips)
    shape_cfg = {"train_4k": (4096, 256), "prefill_32k": (32768, 32),
                 "decode_32k": (32768, 128), "long_500k": (524288, 1)}
    seq, batch = shape_cfg[rec["shape"]]
    mf = model_flops(rec["arch"], rec["kind"], seq, batch)
    ideal_s = mf / (chips * PEAK_FLOPS)
    useful = mf / max(dot_dev * chips, 1)
    bound_s = max(compute_s, memory_s, coll_s)
    dominant = ("compute" if bound_s == compute_s
                else "memory" if bound_s == memory_s else "collective")
    return {
        "cell": rec["cell"],
        "dot_flops_global": dot_dev * chips,
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": coll_s,
        "dominant": dominant,
        "model_flops": mf,
        "hlo_flops_global": flops_dev * chips,
        "useful_ratio": useful,
        "roofline_fraction": ideal_s / max(bound_s, 1e-30),
        "mem_gib": (rec["memory"]["argument_bytes"]
                    + rec["memory"]["temp_bytes"]
                    + rec["memory"]["output_bytes"]
                    - rec["memory"]["alias_bytes"]) / 2**30,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--markdown", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    rows = []
    for path in sorted(glob.glob(os.path.join(DRYRUN_DIR, "*.json"))):
        rec = json.load(open(path))
        if rec.get("status") != "ok" or rec.get("mesh") != args.mesh:
            continue
        rows.append(analyze(rec))

    hdr = ("| cell | compute s | memory s | collective s | dominant | "
           "useful FLOP ratio | roofline frac | GiB/chip |")
    sep = "|" + "---|" * 8
    lines = [hdr, sep]
    for r in rows:
        lines.append(
            f"| {r['cell']} | {r['compute_s']:.3e} | {r['memory_s']:.3e} "
            f"| {r['collective_s']:.3e} | {r['dominant']} "
            f"| {r['useful_ratio']:.2f} | {r['roofline_fraction']:.2%} "
            f"| {r['mem_gib']:.1f} |")
    text = "\n".join(lines)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
        with open(args.out.replace(".md", ".json"), "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
