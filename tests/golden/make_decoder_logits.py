"""Writes ``decoder_logits.npz`` beside this file: the logits the four
families' tiny presets of ``tests/test_decoder.py`` serve (prefill, then
8 decode steps) on the CPU, from the tree it is run in. Run it from the
root of a checkout of the commit the logits are to be held to:

    JAX_PLATFORMS=cpu python tests/golden/make_decoder_logits.py <out.npz>

``tests/test_decoder_nemotron.py`` holds the working tree to the file bit
for bit (a PR that changes a family's arithmetic on purpose makes the
file anew and says so)."""

import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")


def canary():
    """A product, a softmax and a root on this machine: where another
    CPU or XLA rounds them otherwise, bit-equality says nothing."""
    a = jax.random.normal(jax.random.key(0), (96, 64), jnp.float32)
    b = jax.random.normal(jax.random.key(1), (64, 80), jnp.float32)
    p = jax.nn.softmax(jnp.dot(a, b, precision="highest"), axis=-1)
    return np.asarray(p * jax.lax.rsqrt(jnp.mean(a * a) + 1e-5)
                      * jax.nn.silu(p))


def served():
    """``{family: (first, tokens, scores)}`` of the four presets."""
    import pytest

    from predictionio_tpu.models import decoder
    from tests import test_decoder as t
    from tests.test_decoder_granite import GRANITE, INIT

    rng = np.random.default_rng(12)
    out = {}
    for name, base, init, block, lengths, slots in (
            ("lfm2_moe", t.SMALL, None, None, [5, 16, 11, 1, 30, 32], 192),
            ("laguna", t.LAGUNA, t.LAGUNA_INIT, 8, [5, 40, 23, 1, 17, 33],
             240),
            ("xing4_0", t.XING, t.LAGUNA_INIT, 8, [9, 32, 17, 2], 96),
            ("granitemoehybrid", GRANITE, INIT, None, [20, 5, 32, 1, 17, 9],
             128)):
        with pytest.MonkeyPatch.context() as patch:
            if block:
                patch.setattr(decoder, "ATTENTION_BLOCK", block)
            d, cfg, w = t._setup(base=base, init=init)
            first, toks, scores, _ = t._generate(
                w, cfg, t._hists(rng, lengths), slots)
        out[name] = (first, toks, scores)
    return out


if __name__ == "__main__":
    arrays = {"canary": canary()}
    for name, (first, toks, scores) in served().items():
        arrays.update({f"{name}.first": first, f"{name}.tokens": toks,
                       f"{name}.scores": scores})
    np.savez_compressed(sys.argv[1], **arrays)
    print({k: v.shape for k, v in arrays.items()})
