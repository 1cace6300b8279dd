"""The ``generate_latent`` loop: ``generate.py``'s cell (same server,
same load generators, same window, same limits by name) for a
``xing4_0`` configuration: latent attention inside four residual
streams. Its own: which reference decides ``correct``
(``reference_xing.py``) and how it is run so that sequences of up to
4,127 tokens in four float32 streams fit beside the served weights, the
controls (``int8_weights``, ``sinkhorn_1``), and the sizes the roofline
readers take (``required_xing.py``).
"""

from __future__ import annotations

import functools

import numpy as np

from .. import readers, reference_xing as ref
from . import generate

#: tokens a feed-forward call of the reference takes; queries an
#: attention call takes at a time; the lengths sequences are padded to
#: (a multiple of ``STEP`` plus the tokens generated); sequences whose
#: streams are resident at once (4 x 4,128 tokens x 4 streams x 3584 x 4
#: bytes are 0.95 GB beside 8.35 GB of served weights and one layer's
#: 2.9 GB of float32 experts). The configuration's ``check`` may say
#: ``pad_step``, ``query_block`` and ``chunk`` (the rehearsal's does)
BLOCK, QUERY_BLOCK, STEP, CHUNK = 2048, 512, 2048, 4
#: the leaves the ``int8_weights`` control rounds: the shared expert,
#: the dense layers' feed-forward and the latent up-projections
DENSE, SHARED, UP = ("w1", "w3", "w2"), ("s1", "s3", "s2"), ("w_qb", "w_kvb")


class Cell(generate.Cell):
    def _shapes(self) -> None:
        """What the roofline readers need: the batch the window ran at,
        the experts its decode steps touched, and its histories' tokens
        and (query, key) pairs."""
        def series(metric):
            return readers.read(self.facts, {
                "reader": "registry", "metric": metric, "stat": "mean"})

        rows = float(series("pio_batch_occupancy") or 0.0)
        touched = series("pio_moe_experts_touched")
        if not rows or touched is None:
            return  # a program from before the engine: nothing to read
        n = self.lengths.astype(np.float64)
        steps = int(self.traffic["num"]) - 1
        work = {"tokens": rows * float(n.mean()),
                "pairs": rows * float((n * (n + 1) / 2).mean())}
        self.facts["shapes"] = {
            "gen_decode.xing": {
                "cfg": self.model, "rows": rows, "steps": steps,
                "experts_touched": float(touched),
                "cache": float(n.mean()) + steps / 2.0},
            "gen_prefill.xing": {"cfg": self.model, "rows": rows, **work},
            "attn_prefill.xing": {"cfg": self.model, **work}}
        self.say("shapes", {k: {a: b for a, b in v.items() if a != "cfg"}
                            for k, v in self.facts["shapes"].items()})

    # -- the output check, outside every clock ------------------------------
    def _reference_under(self, control):
        """``(cfg, (widen, the sub-blocks' arguments))`` of the
        reference, sound (``None``) or under a control one step below
        the configuration."""
        import jax
        import jax.numpy as jnp

        def widen(lw):
            return jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32), lw)

        trip = jax.jit(ref.int8_round_trip)

        def int8_weights(lw):
            # each leaf is widened OR put through the round trip, never
            # both; a dense layer's w1/w3/w2 are its feed-forward, an
            # expert layer's the routed experts (left sound: PERF.md
            # finding 32.9 has why the check cannot tell them)
            lossy = UP + (SHARED if "gate" in lw else DENSE)
            return {k: trip(v) if k in lossy else v.astype(jnp.float32)
                    for k, v in lw.items()}

        if control is None:
            return self.model, (widen, {})
        if control == "int8_weights":
            return self.model, (int8_weights, {})
        if control == "sinkhorn_1":
            return self.model, (widen, {"sinkhorn_iters": 1})
        raise ValueError(f"unknown control {control!r}")

    def _reference_gaps(self, cfg: dict, how, seqs, firsts, served):
        """Each answer's ``(score gaps, rank gaps)`` against the
        reference's logits at its generated positions (``firsts``: the
        position of the first).

        ``CHUNK`` sequences at a time lie in ONE ``[chunk x stride, n,
        H]`` float32 array, each at its own stride of the longest padded
        length and right-padded to a multiple of ``STEP`` plus the tokens
        generated (the model is causal: what follows a position does not
        move it), and go through the stack layer by layer, so that one
        layer's float32 weights (``widen(layer)``: 2.9 GB of experts) are
        resident at a time; a layer is widened once a chunk. A layer's
        attention sub-block, which mixes a sequence's positions, takes
        one sequence a call with its queries ``QUERY_BLOCK`` at a time;
        its feed-forward sub-block, which takes every token on its own,
        takes the REAL tokens of the chunk, ``BLOCK`` a call. The arrays'
        shapes are the chunk's, whatever lengths the seed sampled: one
        attention program a padded length (two), two feed-forwards
        (dense, experts) and one tail."""
        import jax
        import jax.numpy as jnp

        widen, sub = how
        weights, n = self.weights, int(self.traffic["num"])
        lim = self.config["check"]
        step = int(lim.get("pad_step", STEP))
        queries = int(lim.get("query_block", QUERY_BLOCK))
        chunk = int(lim.get("chunk", CHUNK))
        top = {k: v for k, v in weights.items() if k != "layers"}
        longest = max(int(b) for b in
                      self.config["engine"]["history_buckets"])
        stride = -(-(longest - 1) // step) * step + n
        starts = np.arange(chunk, dtype=np.int64) * stride
        dense = int(cfg["first_k_dense_replace"])
        iters = sub.get("sinkhorn_iters")

        @functools.partial(jax.jit, static_argnames=("l", "size"),
                           donate_argnums=(1,))
        def op(lw, flat, start, *, l, size):
            x = jax.lax.dynamic_slice_in_dim(flat, start, size, 0)
            y = ref.operator(lw, l, x, cfg, query_block=queries, **sub)
            return jax.lax.dynamic_update_slice_in_dim(flat, y, start, 0)

        @functools.partial(jax.jit, static_argnames=("l",),
                           donate_argnums=(1,))
        def ff(lw, flat, at, *, l):
            y = ref.feed_forward(lw, l, flat.at[at].get(
                mode="fill", fill_value=0.0), cfg, sinkhorn_iters=iters)
            return flat.at[at].set(y, mode="drop")

        @jax.jit
        def tail(w, flat, at, tokens, scores):
            x = ref.streams_out(flat[at], cfg)
            return ref.served_gaps(ref.head(w, x, cfg), tokens, scores)

        out = []
        for c in range(0, len(seqs), chunk):
            mine = range(c, min(c + chunk, len(seqs)))
            sizes = [-(-(len(seqs[i]) - n) // step) * step + n
                     for i in mine]
            tokens = np.zeros((chunk * stride,), np.int32)
            for i, a in zip(mine, starts):
                tokens[a:a + len(seqs[i])] = seqs[i]
            # the real tokens' slots, in blocks; a block's spare entries
            # point one past the end: read as zeros, dropped on the way
            # back
            real = np.concatenate([a + np.arange(len(seqs[i]))
                                   for i, a in zip(mine, starts)])
            blocks = np.full(-(-len(real) // BLOCK) * BLOCK, len(tokens),
                             np.int32)
            blocks[:len(real)] = real
            flat = ref.streams_in(ref.embed(top, tokens), cfg)
            for l, lw in enumerate(weights["layers"]):
                lw32 = widen(lw)
                # layer 0 stands for the dense layers, the first expert
                # layer for the rest: the same shapes and equations
                for a, size in zip(starts, sizes):
                    flat = op(lw32, flat, int(a), l=0, size=size)
                for at in blocks.reshape(-1, BLOCK):
                    flat = ff(lw32, flat, at, l=0 if l < dense else dense)
                del lw32
            for i, a in zip(mine, starts):
                s, r = tail(top, flat, int(a) + firsts[i] + np.arange(n),
                            served[i][0], served[i][1].astype(np.float32))
                out.append((np.asarray(s, np.float64),
                            np.asarray(r, np.float64)))
            del flat
        return out
