"""One attention layer's path alone, on the attached device: from the
packed stream ``z [T, H]`` to ``W_o``'s output and the state the decode
goes on from (``models/decoder.py::_attention_prefill`` /
``_latent_prefill``: projections, per-head norm and rotary, the gather
into right-aligned rows, ``ops/window_attention.py``, the gather back,
the head gate, ``W_o``), at the shapes of the two long-history cells.
Wall clock of one jitted call with the device the bottleneck, best of
``--reps``, and with ``--breakdown`` the device's seconds by operation
through the benchmark's own reduction (``cellbench/trace.py``).

Two layouts in one process: the one the tree takes by the heads' width
(in the lanes of the projection where a head fills whole lane tiles),
and heads first (``decoder.LANES`` raised over every width for that
reading: what the tree did at every width until PR 39), with the largest
difference between their outputs. One JSON line a reading.

    python benchmarks/attention_layout_probe.py           # both cells'
    python benchmarks/attention_layout_probe.py --cells tiny   # the CPU

A time from a CPU run is the interpreter's, not a device metric.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: (configuration, layer, rows, stream slots, median and sigma of the
#: lognormal histories): the cells' most frequent rungs, a full layer of
#: 48 heads and a sliding one of 64; ``tiny`` is for the CPU
CELLS = {
    "laguna-full": ("laguna-xs2-l5", 0, 16, 32768, 1024, 0.8),
    "laguna-sliding": ("laguna-xs2-l5", 1, 16, 32768, 1024, 0.8),
    "laguna-full-16k": ("laguna-xs2-l5", 0, 16, 16384, 700, 0.8),
    "xing": ("xing4-29b-a4b-l6", 1, 4, 12288, 2048, 0.6),
    "xing-16k": ("xing4-29b-a4b-l6", 1, 4, 16384, 3500, 0.3),
    "tiny": ("laguna-xs2-l5", 1, 2, 256, 100, 0.5),
}
HISTORY, ROOM = 4096, 32


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", nargs="+", default=[
        "laguna-full", "laguna-sliding", "xing"], choices=sorted(CELLS))
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--breakdown", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, ROOT)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from predictionio_tpu.models import decoder

    dev = jax.devices()[0]
    say = lambda **kv: print(json.dumps(  # noqa: E731
        {"device": dev.device_kind, "platform": dev.platform,
         **kv}), flush=True)

    def best_ms(fn, *a):
        jax.block_until_ready(fn(*a))
        took = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a))
            took.append(time.perf_counter() - t0)
        return 1e3 * min(took)

    def by_operation(fn, *a):
        from cellbench import trace
        with tempfile.TemporaryDirectory() as tmp:
            with jax.profiler.trace(tmp):
                for _ in range(3):
                    jax.block_until_ready(fn(*a))
            red = trace.reduce_device(trace.read_xplane(tmp), 1e9)
        return [[k, round(1e3 * v / 3, 4)]
                for k, v in trace.top_device_ops(red, 14)]

    for name in args.cells:
        config, l, B, T, median, sigma = CELLS[name]
        with open(os.path.join(ROOT, "cellbench", "configs",
                               config + ".json")) as f:
            d = json.load(f)
        history, room = (HISTORY, ROOM) if name != "tiny" else (128, 4)
        if name == "tiny":
            d.update(hidden_size=128, head_dim=16, sliding_window=32,
                     dtype="float32")
        cfg = decoder.DecoderConfig.from_dict(d)
        rng = np.random.default_rng(args.seed)
        lengths = np.clip(rng.lognormal(np.log(median), sigma, B), 16,
                          history).astype(np.int64)
        while (-(-lengths // 16) * 16).sum() > T:  # the stream holds
            lengths = np.maximum(lengths * 15 // 16, 1)  # them, in tiles
        key = jax.random.key(args.seed)
        lw = decoder._draw(key, decoder.INIT, dtype=cfg.dtype, shapes=tuple(
            (k, v) for k, v in sorted(decoder._layer_shapes(cfg, l).items())
            if k[0] != "s" and k not in ("w1", "w2", "w3", "gate",
                                         "gate_bias")
            and not k.startswith("hc_")))
        z = jax.random.normal(jax.random.fold_in(key, 1),
                              (T, cfg.hidden_size))

        def layer(z, lengths):
            _, pos, rows = decoder._row_maps(lengths, T, history, cfg.dtype)
            if cfg.layer_types[l] == decoder.LATENT:
                return decoder._latent_prefill(lw, z, pos, rows, room, cfg)
            return decoder._attention_prefill(lw, z, pos, rows, room, l, cfg)

        outs = {}
        lanes = decoder.LANES
        if name == "tiny":
            lanes = decoder.LANES = 16  # the tiny heads' width
        for layout in ("as_the_tree_takes_it", "heads_first"):
            if layout == "heads_first":
                decoder.LANES = 1 << 30
            try:
                fn = jax.jit(lambda z, n: layer(z, n))  # a trace of its own
                n = jnp.asarray(lengths)
                ms = best_ms(fn, z, n)
                outs[layout] = fn(z, n)[0]
                say(cell=name, layer=l, rows=B, slots=T, layout=layout,
                    real_tokens=int(lengths.sum()), ms=ms,
                    **({"ms_by_operation": by_operation(fn, z, n)}
                       if args.breakdown else {}))
            finally:
                decoder.LANES = lanes
        a, b = outs.values()
        real = np.zeros(T, bool)  # the slots that hold a row's token
        for n, end in zip(lengths, decoder.row_ends(
                lengths, decoder.row_align(history, cfg.dtype))):
            real[end - n:end] = True
        say(cell=name, largest_difference=float(
            jnp.max(jnp.abs(a - b)[real])),
            output_rms=float(jnp.sqrt(jnp.mean(a[real] ** 2))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
