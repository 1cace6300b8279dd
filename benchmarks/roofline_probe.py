"""Roofline accounting for the fused ALS trainer on the attached device.

The round-4 on-chip gram profile showed every hot stage (gather, gram,
solve) running at multi-TF/s while the WHOLE iteration achieves only
0.83 TF/s — so the binding constraint is something the per-stage view
doesn't see. This probe asks XLA itself: it captures the exact
``_train_fused`` invocation ``train_als`` makes (shim capture — zero
argument-assembly duplication), lowers/compiles that same program, and
prints ``cost_analysis()`` (flops, bytes accessed, optimal seconds).

The output places the iteration on the DUAL roofline (ISSUE 7):

- ``arithmetic_intensity`` = XLA flops / XLA bytes accessed, the
  program's position on the x-axis;
- ``attainable_tflops`` = min(peak MXU, intensity x peak HBM GB/s) —
  the roof over that position — and ``bound`` says which segment
  ("hbm" left of the ridge, "mxu" right of it);
- ``hbm_gbps`` / ``hbm_utilization`` (achieved bandwidth) and
  ``achieved_tflops`` / ``mfu`` (achieved compute, padded-work FLOP
  model over the measured steady-state time) say how close the run
  sits to that roof.

With ``PROBE_SERVE=1`` the probe runs the SERVING roofline instead
(ISSUE 13): it lowers the batched top-k dispatch (`_serve_topk`) over
an f32 model and over the row-quantized (``PROBE_QUANT``, default
int8) tables, compares XLA's post-fusion bytes-accessed / arithmetic
intensity / bound for the two programs, and times both dispatches —
the block that proves where the serving bound moved when the wire
went int8 (the fused kernel's VMEM streaming is not visible to XLA's
cost model; its effect shows up in serving_bench's measured lane).

Usage: python benchmarks/roofline_probe.py   (from the repo root)
Env:   BENCH_SCALE, BENCH_RANK as for bench.py; PROBE_ITERS (default 1);
       PROBE_GATHER (float32|bfloat16);
       PROBE_REPEATS (default 3); PROBE_SERVE=1 (+ PROBE_QUANT,
       PROBE_SERVE_ITEMS, PROBE_SERVE_BATCH) for the serving block
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: public spec-sheet HBM bandwidth (GB/s) per generation
PEAK_BW = {"TPU v5 lite": 819, "TPU v5e": 819, "TPU v4": 1228,
           "TPU v5": 2765, "TPU v5p": 2765, "TPU v6e": 1640,
           "TPU v6 lite": 1640}


def _dual_roofline(flops: float, byts: float, bw, peak_fl,
                   wall_s: float | None) -> dict:
    """Shared dual-roofline block: where a program SITS (intensity)
    and which roof is over it, plus achieved bandwidth when timed."""
    out: dict = {"xla_flops": flops, "xla_bytes_accessed": byts}
    if byts and flops:
        ai = flops / byts
        out["arithmetic_intensity"] = round(ai, 3)
        if bw and peak_fl:
            attainable = min(peak_fl, ai * bw * 1e9)
            out["attainable_tflops"] = round(attainable / 1e12, 2)
            out["bound"] = "hbm" if ai * bw * 1e9 < peak_fl else "mxu"
    if wall_s and byts:
        gbps = byts / wall_s / 1e9
        out["hbm_gbps"] = round(gbps, 1)
        if bw:
            out["hbm_utilization"] = round(gbps / bw, 3)
    if wall_s is not None:
        out["wall_s_per_dispatch"] = round(wall_s, 6)
    return out


def serving_roofline() -> dict:
    """The serving-side roofline block (ISSUE 13): the batched top-k
    dispatch over f32 vs row-quantized tables. XLA's bytes-accessed
    for the einsum realization shows the table-read + score-matrix
    traffic the quantized wire shrinks — the `bound` field says
    whether the dispatch is still pinned to the HBM roof after the
    move."""
    import jax

    import predictionio_tpu.models.als as als

    scale = float(os.environ.get("BENCH_SCALE", "1.0"))
    rank = int(os.environ.get("BENCH_RANK", "64"))
    quant = os.environ.get("PROBE_QUANT", "int8")
    n_items = int(os.environ.get("PROBE_SERVE_ITEMS",
                                 str(int(1_200_000 * scale))))
    B = int(os.environ.get("PROBE_SERVE_BATCH", "2048"))
    n_users = max(int(138_000 * scale), B)
    k = 16
    rng = np.random.default_rng(0)
    U = rng.standard_normal((n_users, rank)).astype(np.float32)
    V = rng.standard_normal((n_items, rank)).astype(np.float32)
    idx = rng.integers(0, n_users, B)

    device = jax.devices()[0].device_kind
    bw = next((v for kk, v in PEAK_BW.items() if device.startswith(kk)),
              None)
    try:
        from bench import device_peak_flops

        peak_fl = device_peak_flops()
    except Exception:  # noqa: BLE001 — probe must not die on a moved
        peak_fl = None  # bench.py symbol

    def probe_tables(uf, itf):
        lowered = als._serve_topk.lower(uf, itf, idx, k=k,
                                        n_items=n_items)
        ca = lowered.compile().cost_analysis()
        if isinstance(ca, list):
            ca = ca[0] if ca else {}
        # measured dispatch: warm once, then best-of-3
        als._serve_topk(uf, itf, idx, k=k, n_items=n_items
                        )[0].block_until_ready()
        best = float("inf")
        for _ in range(3):
            t0 = time.monotonic()
            als._serve_topk(uf, itf, idx, k=k, n_items=n_items
                            )[0].block_until_ready()
            best = min(best, time.monotonic() - t0)
        return _dual_roofline(float(ca.get("flops", 0.0)),
                              float(ca.get("bytes accessed", 0.0)),
                              bw, peak_fl, best)

    Ud, Vd = jax.device_put(U), jax.device_put(V)
    f32_block = probe_tables(Ud, Vd)
    # ptpu: allow[quantize-without-parity-gate] — roofline probe
    # measures both table modes offline; nothing serves these tables
    qU = als.QuantizedFactors(*als._quantize_rows(U, quant),
                              quant=quant)
    # ptpu: allow[quantize-without-parity-gate] — same offline probe
    qV = als.QuantizedFactors(*als._quantize_rows(V, quant),
                              quant=quant)
    qU, qV = jax.device_put(qU), jax.device_put(qV)
    q_block = probe_tables(qU, qV)
    out = {
        "metric": "serve_topk_roofline",
        "device": device,
        "rank": rank, "n_items": n_items, "batch": B, "k": k,
        "quant": quant,
        "f32": f32_block,
        quant: q_block,
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                     time.gmtime()),
    }
    if f32_block.get("xla_bytes_accessed") \
            and q_block.get("xla_bytes_accessed"):
        out["bytes_x"] = round(
            f32_block["xla_bytes_accessed"]
            / q_block["xla_bytes_accessed"], 2)
    if f32_block.get("wall_s_per_dispatch") \
            and q_block.get("wall_s_per_dispatch"):
        out["dispatch_x"] = round(
            f32_block["wall_s_per_dispatch"]
            / q_block["wall_s_per_dispatch"], 2)
    return out


def main() -> None:
    if os.environ.get("PROBE_SERVE") == "1":
        print(json.dumps(serving_roofline()))
        return
    scale = float(os.environ.get("BENCH_SCALE", "1.0"))
    rank = int(os.environ.get("BENCH_RANK", "64"))
    iters = int(os.environ.get("PROBE_ITERS", "1"))
    gather = os.environ.get("PROBE_GATHER", "float32")
    n_users = int(138_000 * scale)
    n_items = int(27_000 * scale)
    nnz = int(20_000_000 * scale)

    import jax

    import predictionio_tpu.models.als as als

    rng = np.random.default_rng(0)
    items = (np.random.default_rng(1).zipf(1.3, size=nnz)
             % n_items).astype(np.int32)
    users = rng.integers(0, n_users, nnz).astype(np.int32)
    vals = np.ones(nnz, dtype=np.float32)
    ratings = als.RatingsCOO(users, items, vals, n_users, n_items)
    params = als.ALSParams(rank=rank, num_iterations=iters,
                           implicit_prefs=True, alpha=40.0, reg=0.01,
                           seed=3, gather_dtype=gather)

    captured: dict = {}
    orig = als._train_fused

    def shim(*a, **k):
        captured["a"], captured["k"] = a, k
        return orig(*a, **k)

    packed = als.pack_ratings(ratings, params)
    als._train_fused = shim
    try:
        # warm run: compiles + ships the blocked layout
        U, V = als.train_als(ratings, params, packed=packed)
        np.asarray(jax.device_get(V[0, :1]))  # hard sync
        # steady state: best-of-N repeat runs on the SAME packed
        # problem — the pure compiled-loop time the bench headline
        # measures, no compile or transfer in the denominator
        best = float("inf")
        for _ in range(int(os.environ.get("PROBE_REPEATS", "3"))):
            t0 = time.monotonic()
            U, V = als.train_als(ratings, params, packed=packed)
            np.asarray(jax.device_get(V[0, :1]))
            best = min(best, time.monotonic() - t0)
    finally:
        als._train_fused = orig
    if "a" not in captured:
        print(json.dumps({"error": "train_als did not take the fused "
                                   "path (checkpointing active?)"}))
        return

    lowered = orig.lower(*captured["a"], **captured["k"])
    comp = lowered.compile()
    ca = comp.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0] if ca else {}
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    device = jax.devices()[0].device_kind
    bw = next((v for k, v in PEAK_BW.items() if device.startswith(k)),
              None)
    try:
        from bench import device_peak_flops

        peak_fl = device_peak_flops()
    except Exception:  # noqa: BLE001 — probe must not die on a moved
        peak_fl = None  # bench.py symbol
    per_iter_s = best / max(iters, 1)
    model_fl = als.als_flops_per_iter(packed[0], packed[1], params)
    achieved_fl = model_fl / per_iter_s if per_iter_s else None
    out = {
        "metric": "als_fused_roofline",
        "device": device,
        "gather_dtype": gather,
        "rank": rank, "nnz": nnz, "iters_in_program": iters,
        "xla_flops": flops,
        "xla_bytes_accessed": byts,
        "xla_optimal_seconds": ca.get("optimal_seconds"),
        "steady_state_s_per_iter": round(per_iter_s, 4),
        "model_flops_per_iter": model_fl,
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                     time.gmtime()),
    }
    if achieved_fl:
        out["achieved_tflops"] = round(achieved_fl / 1e12, 3)
        if peak_fl:
            out["mfu"] = round(achieved_fl / peak_fl, 4)
    if byts and best:
        # bytes accessed is XLA's POST-fusion traffic model for the
        # compiled program (iters iterations): achieved bandwidth =
        # bytes / steady-state run time
        gbps = byts / best / 1e9
        out["hbm_gbps"] = round(gbps, 1)
        if bw:
            out["hbm_peak_gbps"] = bw
            out["hbm_utilization"] = round(gbps / bw, 3)
    if byts and flops:
        # dual-roofline position: where the program SITS (intensity)
        # and which roof is over it
        ai = flops / byts
        out["arithmetic_intensity"] = round(ai, 3)
        if bw and peak_fl:
            attainable = min(peak_fl, ai * bw * 1e9)
            out["attainable_tflops"] = round(attainable / 1e12, 2)
            out["bound"] = "hbm" if ai * bw * 1e9 < peak_fl else "mxu"
            if achieved_fl:
                out["roofline_fraction"] = round(
                    achieved_fl / attainable, 3)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
