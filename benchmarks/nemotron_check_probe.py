"""What the ``nemotron3-super-l11`` cell's check would read, without the
server and the window: one batch of the traffic's histories served
through the engine's two programs, then compared with
``cellbench/reference_nemotron.py`` sound and under each control, for
each of a list of ``init`` overrides. This is how the configuration's
``init`` factors and ``check`` limits were chosen (PERF.md, PR 48): the
factors decide what a seeded model makes of its state and of its
experts, and so whether a reference one step below the configuration is
told apart.

    python benchmarks/nemotron_check_probe.py --seeds 3 5 \\
        --init '{}' --init '{"expert_out": 0.5}' [--rehearse]

The weights are the configuration's (``weights_seed``), as in the cell;
a seed draws the batch. On the chip at the cell's size (16 rows);
``--rehearse`` runs the configuration's ``rehearse`` size on the CPU. One
line a reading.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELL = "nemotron3-super-l11.gen32-hist192-closed48"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", default=[3])
    ap.add_argument("--init", action="append", default=None,
                    help="a JSON object over the configuration's init")
    ap.add_argument("--controls", nargs="+",
                    default=["sound", "state_bf16", "int8_weights",
                             "int8_routed"])
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import numpy as np

    from cellbench import data, manifest
    from cellbench.loops import generate, generate_hybrid
    from predictionio_tpu.models import decoder
    from predictionio_tpu.templates.generative import (
        GenerativeAlgorithm, GenerativeModel, GenerativeParams)

    man = manifest.load()
    cell = manifest.cell(man, CELL)
    config = manifest.read_json(os.path.join(
        manifest.ROOT, manifest.config_of(man, cell)["file"]))
    traffic = manifest.read_json(manifest.traffic_path(cell["traffic"]))
    if args.rehearse:
        config = {**config, **config["rehearse"]}
        traffic = {**traffic, **traffic["rehearse"]}
    c = generate_hybrid.Cell.__new__(generate_hybrid.Cell)
    c.config, c.traffic = config, traffic
    c.model = generate.model_keys(config)
    cfg = decoder.DecoderConfig.from_dict(c.model)
    rows = int(config["engine"]["row_buckets"][-1])
    algo = GenerativeAlgorithm(GenerativeParams(
        model=c.model, max_new=int(traffic["num"]), **config["engine"]))
    print("device", jax.devices()[0].device_kind, flush=True)
    for over in [json.loads(s) for s in (args.init or ["{}"])]:
        init = {**config["init"], **over}
        for seed in args.seeds:
            rng = np.random.default_rng([seed, 0x9e4])
            lengths = rng.permutation(
                generate.history_lengths(traffic, 3 * rows))[:rows]
            lengths[0] = int(traffic["history"]["max"])  # one longest row
            tokens = data.sample_entities(
                rng, cfg.vocab_size, int(lengths.sum()), traffic.get("zipf"))
            ends = np.cumsum(lengths)
            c.histories = [tokens[e - k:e].tolist()
                           for e, k in zip(ends, lengths)]
            c.weights = generate_hybrid.weights_of(
                {**config, "init": init}, cfg)
            model = GenerativeModel(config=c.model, seed=seed,
                                    weights=c.weights)
            arrays, _ = algo._dispatch(model, c.histories)
            toks, scores = (np.asarray(a) for a in arrays[:2])
            parsed = {i: (toks[i], scores[i]) for i in range(rows)}
            # what the router made of the batch, over the experts held
            held = list(cfg.experts_held or range(cfg.num_experts))
            prefill, decode = (np.asarray(a) for a in arrays[2])
            mine = prefill[:, held].astype(np.float64)
            print("routing", json.dumps({
                "init": over, "seed": seed,
                "touched": round(float(
                    (decode[..., held] > 0).sum(-1).mean()), 2),
                "imbalance": round(float(
                    (mine.max(1) / mine.mean(1)).mean()), 2),
                "held_pct": round(100.0 * float(
                    (prefill[:, held].sum() + decode[..., held].sum())
                    / (prefill.sum() + decode.sum())), 2)}), flush=True)
            for control in args.controls:
                t0 = time.perf_counter()
                read = c._compare(parsed,
                                  None if control == "sound" else control)
                print("reading", json.dumps({
                    "init": over, "seed": seed, "control": control,
                    **{k: round(read[k], 5) for k in (
                        "score_gap_p50", "score_gap_max", "rank_gap_max",
                        "score_gap_p90", "score_gap_p99",
                        "greedy_agrees_share")},
                    "seconds": round(time.perf_counter() - t0, 1)}),
                    flush=True)
            del c.weights, model
    return 0


if __name__ == "__main__":
    sys.exit(main())
