"""The ``generate_long`` loop: ``generate.py``'s cell (same server,
same load generators, same window, same limits by name) for a
``laguna`` configuration under long histories. Its own: which reference
decides ``correct`` (``reference_laguna.py``) and how it is run so that
sequences of up to 4,127 tokens fit beside the served weights, the
controls (``int8_experts``, ``no_window``; ``int8_routed`` is a probe),
and the sizes the roofline readers take (``required_laguna.py``).
"""

from __future__ import annotations

import functools

import numpy as np

from .. import readers, reference_laguna as ref, required_laguna
from . import generate

#: tokens a feed-forward call of the reference takes; queries an
#: attention call takes at a time; the lengths sequences are padded to
#: (a multiple of ``STEP`` plus the tokens generated): two programs a
#: layer kind, whatever the seed sampled. The configuration's ``check``
#: may say ``pad_step`` and ``query_block`` (the rehearsal's does)
BLOCK, QUERY_BLOCK, STEP = 2048, 512, 2048


class Cell(generate.Cell):
    def _shapes(self) -> None:
        """What the roofline readers need: the batch the window ran at,
        the experts its decode steps touched, and its histories' tokens
        and (query, key) pairs, full and under the window."""
        def series(metric):
            return readers.read(self.facts, {
                "reader": "registry", "metric": metric, "stat": "mean"})

        rows = float(series("pio_batch_occupancy") or 0.0)
        touched = series("pio_moe_experts_touched")
        if not rows or touched is None:
            return  # a program from before the engine: nothing to read
        n = self.lengths.astype(np.float64)
        W = float(self.model["sliding_window"])
        steps = int(self.traffic["num"]) - 1
        work = {"tokens": rows * float(n.mean()),
                "pairs_full": rows * float((n * (n + 1) / 2).mean()),
                "pairs_window": rows * float(np.mean(
                    [required_laguna.window_pairs(v, W) for v in n]))}
        self.facts["shapes"] = {
            "gen_decode.laguna": {
                "cfg": self.model, "rows": rows, "steps": steps,
                "experts_touched": float(touched),
                "cache_full": float(n.mean()) + steps / 2.0,
                "cache_window": float(
                    np.minimum(n + steps / 2.0, W).mean())},
            "gen_prefill.laguna": {"cfg": self.model, "rows": rows, **work},
            "attn_prefill.laguna": {"cfg": self.model, **work}}
        self.say("shapes", {k: {a: b for a, b in v.items() if a != "cfg"}
                            for k, v in self.facts["shapes"].items()})

    # -- the output check, outside every clock ------------------------------
    def _reference_under(self, control):
        """``(cfg, (widen, attention's arguments))`` of the reference,
        sound (``None``) or under a control one step below the
        configuration."""
        import jax
        import jax.numpy as jnp

        def widen(lw):
            return jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32), lw)

        def int8(*lossy):
            # an expert layer's leaves named ``lossy``; each leaf is
            # widened OR put through the round trip, never both (3.2 GB)
            trip = jax.jit(ref.int8_round_trip)

            def widen_or_round(lw):
                return {k: trip(v) if k in lossy and "gate" in lw
                        else v.astype(jnp.float32) for k, v in lw.items()}
            return widen_or_round

        routed, shared = ("w1", "w3", "w2"), ("s1", "s3", "s2")
        if control is None:
            return self.model, (widen, {})
        if control == "int8_experts":
            return self.model, (int8(*routed, *shared), {})
        if control == "int8_routed":
            # a probe, not a control: what ``correct`` makes of the
            # routed experts alone (PERF.md finding 32.9)
            return self.model, (int8(*routed), {})
        if control == "no_window":
            return self.model, (widen, {"window": None})
        raise ValueError(f"unknown control {control!r}")

    def _reference_gaps(self, cfg: dict, how, seqs, firsts, served):
        """Each answer's ``(score gaps, rank gaps)`` against the
        reference's logits at its generated positions (``firsts``: the
        position of the first).

        The reference goes layer by layer, so that one layer's float32
        weights (``widen(layer)``: 3.2 GB of experts) are resident at a
        time. All sequences lie in ONE ``[sample x stride, H]`` float32
        array, each at its own stride of the longest padded length and
        right-padded to a multiple of ``STEP`` plus the tokens generated
        (the model is causal: what follows a position does not move it). A layer's operator, which mixes a
        sequence's positions, takes one sequence a call with its queries
        ``QUERY_BLOCK`` at a time (scores ``[8, 512, T]`` a key-value
        head); its feed-forward, which takes every token on its own,
        takes the REAL tokens of all sequences, ``BLOCK`` a call. So the
        check compiles one operator program a layer kind and padded
        length (four), two feed-forwards and one tail, whatever the seed
        sampled, and pays for no padding where the work is."""
        import jax
        import jax.numpy as jnp

        widen, attention = how
        weights, n = self.weights, int(self.traffic["num"])
        step = int(self.config["check"].get("pad_step", STEP))
        queries = int(self.config["check"].get("query_block", QUERY_BLOCK))
        top = {k: v for k, v in weights.items() if k != "layers"}
        sizes = [-(-(len(s) - n) // step) * step + n for s in seqs]
        # every sequence has a stride of the longest padded length in an
        # array sized by the SAMPLE'S size: its shape, and so every
        # program below, is the same whatever lengths the seed sampled
        longest = max(int(b) for b in
                      self.config["engine"]["history_buckets"])
        stride = -(-(longest - 1) // step) * step + n
        rows = max(int(self.traffic["check_sample"]), len(seqs))
        starts = np.arange(rows, dtype=np.int64) * stride
        tokens = np.zeros((rows * stride,), np.int32)
        for seq, a in zip(seqs, starts):
            tokens[a:a + len(seq)] = seq
        # the real tokens' slots, in blocks; a block's spare entries
        # point one past the end: read as zeros, dropped on the way back
        real = np.concatenate([a + np.arange(len(seq))
                               for seq, a in zip(seqs, starts)])
        blocks = np.full(-(-len(real) // BLOCK) * BLOCK, len(tokens),
                         np.int32)
        blocks[:len(real)] = real
        blocks = blocks.reshape(-1, BLOCK)

        @functools.partial(jax.jit, static_argnames=("l", "size"),
                           donate_argnums=(1,))
        def op(lw, flat, start, *, l, size):
            x = jax.lax.dynamic_slice_in_dim(flat, start, size, 0)
            y = ref.operator(lw, l, x, cfg, query_block=queries,
                             **attention)
            return jax.lax.dynamic_update_slice_in_dim(flat, y, start, 0)

        @functools.partial(jax.jit, static_argnames=("l",),
                           donate_argnums=(1,))
        def ff(lw, flat, at, *, l):
            y = ref.feed_forward(lw, l, flat.at[at].get(
                mode="fill", fill_value=0.0), cfg)
            return flat.at[at].set(y, mode="drop")

        @jax.jit
        def tail(w, flat, at, tokens, scores):
            return ref.served_gaps(ref.head(w, flat[at], cfg), tokens,
                                   scores)

        # the first layer of each kind stands for its kind: the same
        # shapes and the same equations, so the same program
        kinds = [((cfg["layer_types"][l],
                   cfg["num_attention_heads_per_layer"][l]),
                  cfg["mlp_layer_types"][l])
                 for l in range(len(weights["layers"]))]
        flat = ref.embed(top, tokens)
        for l, lw in enumerate(weights["layers"]):
            lw32 = widen(lw)
            same_op = [k[0] for k in kinds].index(kinds[l][0])
            same_ff = [k[1] for k in kinds].index(kinds[l][1])
            for a, size in zip(starts, sizes):
                flat = op(lw32, flat, int(a), l=same_op, size=size)
            for at in blocks:
                flat = ff(lw32, flat, at, l=same_ff)
            del lw32
        out = []
        for i, a in enumerate(starts[:len(seqs)]):
            s, r = tail(top, flat, int(a) + firsts[i] + np.arange(n),
                        served[i][0], served[i][1].astype(np.float32))
            out.append((np.asarray(s, np.float64),
                        np.asarray(r, np.float64)))
        return out
