"""The ``generate_ssm`` loop: ``generate.py``'s cell (same server, same
load generators, same window, same limits by name) for a
``granitemoehybrid`` configuration: state-space layers beside a few
grouped-query ones. Its own: which reference decides ``correct``
(``reference_granite.py``, whose recurrence runs token by token) and
how it is run so that it fits beside the served weights, the controls
(``state_bf16``, ``int8_weights``), and the sizes the roofline readers
take (``required_granite.py``).
"""

from __future__ import annotations

import functools

import numpy as np

from .. import readers, reference_granite as ref, required_granite
from . import generate

#: sequences an operator call of the reference takes (each on its own:
#: ``jax.vmap``; 8 x 1,056 tokens x 32 heads of scores are 1.1 GB), and
#: tokens a feed-forward call takes. The configuration's ``check`` may
#: say ``chunk`` (the rehearsal's does)
CHUNK, BLOCK = 8, 2048
#: the leaves the ``int8_weights`` control rounds: a state-space mixer's
#: in- and out-projection and every feed-forward
LOSSY = ("w_in", "w_out", "w1", "w3", "w2")


class Cell(generate.Cell):
    def _shapes(self) -> None:
        """What the roofline readers need: the batch the window ran at
        and its histories' tokens, (query, key) pairs and same-row pairs
        inside the scan's chunks."""
        rows = float(readers.read(self.facts, {
            "reader": "registry", "metric": "pio_batch_occupancy",
            "stat": "mean"}) or 0.0)
        if not rows:
            return
        n = self.lengths.astype(np.float64)
        steps = int(self.traffic["num"]) - 1
        chunk = int(self.model["mamba_chunk_size"])
        work = {"rows": rows, "tokens": rows * float(n.mean()),
                "scan_pairs": rows * float(np.mean(
                    [required_granite.chunk_pairs(k, chunk)
                     for k in self.lengths]))}
        self.facts["shapes"] = {
            "ssm_scan.granite": {"cfg": self.model, **work},
            "ssm_step.granite": {"cfg": self.model, "rows": rows,
                                 "steps": steps},
            "gen_prefill.granite": {
                "cfg": self.model, **work,
                "pairs": rows * float((n * (n + 1) / 2).mean())},
            "gen_decode.granite": {
                "cfg": self.model, "rows": rows, "steps": steps,
                "cache": float(n.mean()) + steps / 2.0}}
        self.say("shapes", {k: {a: b for a, b in v.items() if a != "cfg"}
                            for k, v in self.facts["shapes"].items()})

    # -- the output check, outside every clock ------------------------------
    def _reference_under(self, control):
        """``(cfg, (widen, what every token's new state goes through))``
        of the reference, sound (``None``) or under a control one step
        below the configuration."""
        import jax
        import jax.numpy as jnp

        def widen(lw):
            return jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32), lw)

        trip = jax.jit(ref.int8_round_trip)

        def int8_weights(lw):
            return {k: trip(v) if k in LOSSY else v.astype(jnp.float32)
                    for k, v in lw.items()}

        if control is None:
            return self.model, (widen, None)
        if control == "state_bf16":
            return self.model, (widen, ref.round_bf16)
        if control == "int8_weights":
            return self.model, (int8_weights, None)
        raise ValueError(f"unknown control {control!r}")

    def _reference_gaps(self, cfg: dict, how, seqs, firsts, served):
        """Each answer's ``(score gaps, rank gaps)`` against the
        reference's logits at its generated positions (``firsts``: the
        position of the first).

        The reference goes layer by layer, so that one layer's float32
        weights are resident at a time. A layer's operator, which mixes
        a sequence's positions (the recurrence token by token, attention
        over every earlier key), takes ``CHUNK`` sequences a call, each
        on its own (``jax.vmap`` of the one-sequence function) and
        right-padded to the longest history bucket plus the tokens
        generated (the model is causal: what follows a position does not
        move it). Its feed-forward, which takes every token on its own,
        takes the REAL tokens of all sequences, ``BLOCK`` a call. So the
        check compiles five programs whatever the seed sampled."""
        import jax
        import jax.numpy as jnp

        widen, round_state = how
        weights, n = self.weights, int(self.traffic["num"])
        chunk = int(self.config["check"].get("chunk", CHUNK))
        top = {k: v for k, v in weights.items() if k != "layers"}
        T = max(int(b) for b in
                self.config["engine"]["history_buckets"]) + n
        N = -(-len(seqs) // chunk) * chunk
        tokens = np.zeros((N, T), np.int32)
        for i, seq in enumerate(seqs):
            tokens[i, :len(seq)] = seq
        # the real tokens' slots in the flat [N * T] layout, in blocks;
        # a block's spare entries point one past the end: read as
        # zeros, dropped on the way back
        real = np.concatenate([i * T + np.arange(len(seq))
                               for i, seq in enumerate(seqs)])
        blocks = np.full(-(-len(real) // BLOCK) * BLOCK, N * T, np.int32)
        blocks[:len(real)] = real

        ops = {}
        for l, kind in enumerate(cfg["layer_types"]):
            if kind not in ops:  # the first layer of a kind stands for it
                ops[kind] = jax.jit(lambda lw, x, l=l: jax.vmap(
                    lambda one: ref.operator(lw, l, one, cfg,
                                             round_state))(x))

        @functools.partial(jax.jit, donate_argnums=(1,))
        def ff(lw, flat, at):
            y = ref.feed_forward(lw, 0, flat.at[at].get(
                mode="fill", fill_value=0.0), cfg)
            return flat.at[at].set(y, mode="drop")

        @jax.jit
        def tail(w, x, at, tokens, scores):
            def one(x, at, tokens, scores):
                return ref.served_gaps(ref.head(w, x[at], cfg), tokens,
                                       scores)
            return jax.vmap(one)(x, at, tokens, scores)

        x = ref.embed(top, tokens, cfg)
        for l, lw in enumerate(weights["layers"]):
            lw32 = widen(lw)
            op = ops[cfg["layer_types"][l]]
            flat = jnp.concatenate(
                [op(lw32, x[c:c + chunk]) for c in range(0, N, chunk)]
            ).reshape(N * T, -1)
            del x
            for at in blocks.reshape(-1, BLOCK):
                flat = ff(lw32, flat, at)
            x = flat.reshape(N, T, -1)
            del lw32, flat

        out = []
        for c in range(0, N, chunk):
            rows = [min(i, len(seqs) - 1) for i in range(c, c + chunk)]
            at = np.array([firsts[i] for i in rows])[:, None] + np.arange(n)
            s, r = tail(top, x[c:c + chunk], at,
                        np.stack([served[i][0] for i in rows]),
                        np.stack([served[i][1] for i in rows]
                                 ).astype(np.float32))
            out += list(zip(np.asarray(s, np.float64),
                            np.asarray(r, np.float64)))
        return out[:len(seqs)]
