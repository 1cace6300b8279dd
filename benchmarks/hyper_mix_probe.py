"""One sub-block's residual path alone, on the attached device:
``models/decoder.py::_sub_block`` around the identity over ``[n, slots,
H]`` float32 streams, as the two kernels of ``ops/hyper_mix.py`` and as
the plain lines a stream under a tile's tokens takes (the tile raised
over the stream for that reading). Wall clock of chains of ``--chain``
sub-blocks in one ``lax.scan`` with the device the bottleneck, best of
``--reps``; beside them each kernel alone at each ``--tiles`` entry, and
the largest difference between the two forms' ``pre``, ``post``,
``res``, ``z`` and ``x'``. One JSON line a reading.

    python benchmarks/hyper_mix_probe.py               # the xing4 cell's
    python benchmarks/hyper_mix_probe.py --slots 256 --width 128   # CPU

A time from a CPU run is the interpreter's, not a device metric.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from predictionio_tpu.models import decoder  # noqa: E402
from predictionio_tpu.ops import hyper_mix  # noqa: E402

CONFIG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "cellbench", "configs", "xing4-29b-a4b-l6.json")


def best_ms(fn, *args, reps: int) -> float:
    jax.block_until_ready(fn(*args))
    took = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        took.append(time.perf_counter() - t0)
    return 1e3 * min(took)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, nargs="+",
                    default=[8192, 12288, 16384])
    ap.add_argument("--width", type=int, default=None)
    ap.add_argument("--tiles", type=int, nargs="+", default=[64, 128])
    ap.add_argument("--chain", type=int, default=4)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    with open(CONFIG) as f:
        d = json.load(f)
    if args.width:
        d["hidden_size"] = args.width
    cfg = decoder.DecoderConfig.from_dict(d)
    n, H = cfg.hc_mult, cfg.hidden_size
    key = jax.random.key(args.seed)
    lw = decoder._draw(key, decoder.INIT, dtype="float32", shapes=tuple(
        (k, v) for k, v in sorted(decoder._layer_shapes(cfg, 0).items())
        if k.startswith("hc_op_") or k == "op_norm"))
    dev = jax.devices()[0]
    say = lambda **kv: print(json.dumps(  # noqa: E731
        {"device": dev.device_kind, "platform": dev.platform, **kv}),
        flush=True)
    how = dict(eps=cfg.hc_eps, norm_eps=cfg.norm_eps,
               iters=cfg.hc_sinkhorn_iters,
               clamp=(cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max))
    cols = decoder._hc_columns(lw, "op", n)

    def chain():  # a new function a call: a trace of its own
        def one(x, _):
            new, _, gap = decoder._sub_block(
                lw, "op", x, lambda z: (z, None), cfg)
            return new, gap
        return jax.jit(lambda x: jax.lax.scan(one, x, None,
                                              length=args.chain))

    for T in args.slots:
        # streams that differ, about unit RMS, the scale a sub-block meets
        x = jax.random.normal(jax.random.fold_in(key, T), (n, T, H)) \
            * jnp.array([1.0, 2.0, 0.5, 1.5][:n])[:, None, None]
        ms = {}
        ms["kernels"] = best_ms(chain(), x, reps=args.reps) / args.chain
        tile = hyper_mix.TILE
        hyper_mix.TILE = T + 1  # the plain lines, whatever the stream
        try:
            ms["plain"] = best_ms(chain(), x, reps=args.reps) / args.chain
            plain = jax.jit(lambda x: (
                decoder._hc_coefficients(lw, "op", x, cfg),
                decoder._sub_block(lw, "op", x, lambda z: (z, None), cfg)))
            (pre, post, res), (new, _, gap) = plain(x)
        finally:
            hyper_mix.TILE = tile
        u = sum(pre[j][:, None] * x[j] for j in range(n))
        z0 = decoder._rms(u, lw["op_norm"], cfg.norm_eps)
        z, coef = hyper_mix.hyper_mix_read(x, *cols, lw["op_norm"], **how)
        got = hyper_mix.coefficients(coef, n)
        k_new, _, k_gap = jax.jit(lambda x: decoder._sub_block(
            lw, "op", x, lambda z: (z, None), cfg))(x)
        far = {name: float(jnp.max(jnp.abs(a - b))) for name, a, b in (
            ("pre", pre, got[0]), ("post", post, got[1]),
            ("res", res, got[2]), ("z", z0, z), ("x_new", new, k_new))}
        say(slots=T, width=H, ms_a_sub_block=ms, largest_difference=far,
            gap=[float(gap), float(k_gap)],
            bytes_two_reads_one_write=3 * x.nbytes + 2 * z.nbytes)
        for t in args.tiles:
            read = jax.jit(lambda x, t=t: hyper_mix.hyper_mix_read(
                x, *cols, lw["op_norm"], tile=t, **how))
            write = jax.jit(lambda x, z, coef, t=t: hyper_mix.hyper_mix_write(
                x, z, coef, tile=t), donate_argnums=(0,))
            r = best_ms(read, x, reps=args.reps)
            took = []
            for _ in range(args.reps + 1):
                xx = x + 0.0
                jax.block_until_ready(xx)
                t0 = time.perf_counter()
                jax.block_until_ready(write(xx, z, coef))
                took.append(time.perf_counter() - t0)
            say(slots=T, tile=t, read_ms=r, write_ms=1e3 * min(took[1:]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
