"""Roofline analysis from the dry-run artifacts (deliverable g).

Reads experiments/dryrun/*.json (launch/dryrun.py) and derives the three
roofline terms per (arch x shape x mesh) against the peaks of the chip each
record was compiled for (``PEAKS``, keyed by ``device_kind``):

  compute_s    = HLO_FLOPs_global / (chips * peak FLOP/s)
               = per-device HLO flops / peak      (SPMD: HLO is per-chip)
  memory_s     = per-device HLO bytes / HBM bytes/s
  collective_s = per-device wire bytes / ICI bytes/s

FLOPs/bytes/wire come from the trip-count-corrected analyzer
(repro/launch/hlo_cost.py): XLA's own ``cost_analysis()`` counts while-loop
bodies once, undercounting every scanned model by orders of magnitude.

Reported per cell:
  * the three terms and the dominant (= bottleneck) one,
  * MODEL_FLOPS (6*N_active*tokens train / 2*N_active*tokens prefill /
    2*N_active*batch decode) and MODEL_FLOPS / HLO_FLOPs_global — the
    useful-compute ratio (remat, attention, vocab, padding show up here),
  * roofline fraction = ideal_s / bound_s where ideal_s is the physical
    lower bound for the step: compute-limited for train/prefill
    (MODEL_FLOPS at peak), traffic-limited for decode (weights + caches
    must stream from HBM once: argument bytes / HBM bw).
"""

from __future__ import annotations

import glob
import json
import os

# Per-chip peaks by ``jax.Device.device_kind``. TPU v5e: Google Cloud
# documentation, "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s HBM; 1,600 Gbit/s
# interconnect over 4 links, so 50 GB/s per link.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm": 819e9, "link": 50e9},
}


def peaks(device_kind: str) -> dict:
    """The peak table row of ``device_kind``; an unknown chip is an error
    (no default: a roofline against the wrong chip is a wrong number)."""
    if device_kind not in PEAKS:
        raise KeyError(f"no roofline peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]

HERE = os.path.dirname(__file__)
DRYRUN = os.path.join(HERE, "..", "experiments", "dryrun")


def model_flops(rec: dict) -> float:
    n = rec.get("n_params", 0)
    na = rec.get("n_active_params", n)
    b = rec.get("global_batch", 1)
    s = rec.get("seq_len", 1)
    step = rec.get("step")
    if step == "train":
        return 6.0 * na * b * s
    if step == "prefill":
        return 2.0 * na * b * s
    if step == "decode":
        return 2.0 * na * b  # one token per sequence
    return 0.0


def analyse(rec: dict) -> dict:
    dev = rec["n_devices"]
    pk = peaks(rec["device_kind"])
    hc = rec.get("hlo_cost") or {}
    fl = hc.get("flops", rec["cost"]["flops"])  # per-device
    by = hc.get("bytes", rec["cost"]["bytes_accessed"])
    wire = hc.get("collective_wire_bytes",
                  rec["collectives"]["total_wire_bytes"])
    compute_s = fl / pk["flops"]
    memory_s = by / pk["hbm"]
    coll_s = wire / pk["link"]
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": coll_s}
    dom = max(terms, key=terms.get)
    bound_s = max(terms.values())
    mf = model_flops(rec)
    glob_fl = fl * dev

    if rec.get("step") == "decode":
        # Decode is traffic-limited: weights + caches stream once.
        arg_bytes = rec.get("memory", {}).get("argument_bytes", 0)
        ideal_s = arg_bytes / pk["hbm"]
    else:
        ideal_s = (mf / dev) / pk["flops"]
    frac = min(1.0, ideal_s / bound_s) if (ideal_s and bound_s) else 0.0

    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "devices": dev,
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": coll_s, "dominant": dom, "bound_s": bound_s,
        "model_flops": mf, "hlo_flops_global": glob_fl,
        "useful_ratio": (mf / glob_fl) if glob_fl else 0.0,
        "ideal_s": ideal_s,
        "roofline_fraction": frac,
    }


def load(out_dir: str = DRYRUN) -> list[dict]:
    rows = []
    for path in sorted(glob.glob(os.path.join(out_dir, "*.json"))):
        rec = json.load(open(path))
        if rec.get("status") != "ok":
            continue
        rows.append(analyse(rec))
    return rows


def fmt_s(x: float) -> str:
    if x >= 1:
        return f"{x:.2f}s"
    if x >= 1e-3:
        return f"{x * 1e3:.2f}ms"
    return f"{x * 1e6:.1f}us"


def table(rows: list[dict], mesh: str = "single") -> str:
    lines = [
        "| arch | shape | compute | memory | collective | bound | "
        "useful-FLOP ratio | roofline frac |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        if r["mesh"] != mesh:
            continue
        lines.append(
            f"| {r['arch']} | {r['shape']} | {fmt_s(r['compute_s'])} | "
            f"{fmt_s(r['memory_s'])} | {fmt_s(r['collective_s'])} | "
            f"**{r['dominant']}** | {r['useful_ratio']:.2f} | "
            f"{r['roofline_fraction']:.3f} |"
        )
    return "\n".join(lines)


def run() -> list[dict]:
    rows = load()
    print(f"\nroofline: {len(rows)} compiled cells ({DRYRUN})")
    print(table(rows, "single"))
    from benchmarks import common
    for r in rows:
        if r["mesh"] != "single":
            continue
        common.row(
            "roofline", f"{r['arch']}/{r['shape']}",
            dominant=r["dominant"],
            bound_ms=1e3 * r["bound_s"],
            useful=round(r["useful_ratio"], 3),
            frac=round(r["roofline_fraction"], 3),
        )
    return rows


if __name__ == "__main__":
    run()
