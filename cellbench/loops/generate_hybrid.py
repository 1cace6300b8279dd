"""The ``generate_hybrid`` loop: ``generate.py``'s cell (same server, same
load generators, same window, same limits by name) for a ``nemotron_h``
configuration cut to a chip's share: layers of ONE sub-block each
(state-space mixers over several groups, a few grouped-query layers,
relu² experts in a latent of which this chip holds some). Its own:
which reference decides ``correct`` (``reference_nemotron.py``: the
recurrence token by token, every held expert applied densely) and how
it is run so that it fits beside the served weights, the controls
(``state_bf16``, ``int8_weights``, ``int8_routed``), and the sizes the readers
take (``required_nemotron.py``); and WHERE THE WEIGHTS' KEY COMES FROM:
the configuration's ``weights_seed``, not ``--seed``, which orders the
traffic alone (as the histories' multiset comes from the traffic's
``data_seed``). A seeded router is not a balanced one: the held experts
a decode step touches, and with them the step's bytes and
``served_qps``, follow the router's draw by 6 % (PERF.md, finding 48.2),
which is the draw's and says nothing of the code measured.
"""

from __future__ import annotations

import functools

import numpy as np

from .. import readers, reference_nemotron as ref, required_nemotron
from . import generate

#: sequences a mixer's call of the reference takes (each on its own:
#: ``jax.vmap``; 8 x 1,056 tokens x 32 heads of scores are 1.1 GB), and
#: tokens an expert layer's call takes (128 experts' hidden activations
#: one after the other: 2,048 x 2,688 float32 are 22 MB an expert). The
#: configuration's ``check`` may say ``chunk`` (the rehearsal's does)
CHUNK, BLOCK = 8, 2048
#: the leaves each weight control puts through the int8 round trip:
#: ``int8_weights`` a mixer's in- and out-projection, the latent's
#: projections, the shared expert and the routed experts;
#: ``int8_routed`` the routed experts' two matrices alone
LOSSY = {"int8_weights": ("w_in", "w_out", "w_down", "w_up", "s1", "s2",
                          "w1", "w2"),
         "int8_routed": ("w1", "w2")}


def weights_of(config: dict, cfg):
    """The configuration's weights, on the device."""
    import jax

    from predictionio_tpu.models import decoder

    seed = int(config["weights_seed"])
    key = jax.random.fold_in(jax.random.key(seed >> 31, impl="rbg"),
                             seed & 0x7fffffff)
    return jax.block_until_ready(
        decoder.init_weights(key, cfg, config["init"]))


class Cell(generate.Cell):
    def inputs(self) -> None:
        """``generate.Cell``'s inputs, then the weights drawn again from
        the configuration's ``weights_seed`` (the key ``generate.Cell``
        makes of a seed). The run's own draw is released first: one set
        of weights is resident at a time; the stage takes 6.0 s with the
        second draw as it took without (my chip runs, PR 48)."""
        super().inputs()
        self.weights = None
        self.weights = weights_of(self.config, self.cfg)

    def _shapes(self) -> None:
        """What the roofline readers need: the batch the window ran at,
        its histories' tokens, (query, key) pairs and same-row pairs
        inside the scan's chunks, the HELD experts a decode step touched
        a layer and the share of the router's assignments that landed on
        held experts (both read off the engine's counters; the share is
        the configuration's where a program counts no assignments)."""
        def series(metric, stat="mean", **labels):
            return readers.read(self.facts, {
                "reader": "registry", "metric": metric, "stat": stat,
                "labels": labels})

        rows = float(series("pio_batch_occupancy") or 0.0)
        touched = series("pio_moe_experts_touched")
        if not rows or touched is None:
            return
        share = readers.read(self.facts, {
            "reader": "registry_ratio",
            "metric": "pio_moe_assignments_total",
            "labels": {"where": "held"}, "scale": 1.0,
            "over": [{"where": "held"}, {"where": "absent"}]})
        if share is None:
            share = float(self.model["n_routed_experts"]) \
                / float(self.model["router_experts"])
        n = self.lengths.astype(np.float64)
        steps = int(self.traffic["num"]) - 1
        chunk = int(self.model["chunk_size"])
        work = {"cfg": self.model, "rows": rows,
                "tokens": rows * float(n.mean()),
                "scan_pairs": rows * float(np.mean(
                    [required_nemotron.chunk_pairs(k, chunk)
                     for k in self.lengths])),
                "pairs": rows * float((n * (n + 1) / 2).mean()),
                "held_share": float(share)}
        step = {"cfg": self.model, "rows": rows, "steps": steps,
                "experts_touched": float(touched),
                "held_share": float(share),
                "cache": float(n.mean()) + steps / 2.0}
        self.facts["shapes"] = {
            "ssm_scan.nemotron": work, "moe_prefill.nemotron": work,
            "gen_prefill.nemotron": work, "ssm_step.nemotron": step,
            "moe_step.nemotron": step, "gen_decode.nemotron": step}
        self.say("shapes", {k: {a: b for a, b in v.items() if a != "cfg"}
                            for k, v in (("prefill", work),
                                         ("decode", step))})

    # -- the output check, outside every clock ------------------------------
    def _reference_under(self, control):
        """``(cfg, (what a layer's weights go through, what every token's
        new state goes through))`` of the reference, sound (``None``) or
        under a control one step below the configuration. The reference
        widens a weight to float32 WHERE IT USES IT (an expert at a time
        inside its loop over the held experts), so the served bfloat16
        leaves go in as they are: no float32 copy of a layer's 128
        experts (2.8 GB) lies beside the served weights."""
        import jax
        import jax.numpy as jnp

        def as_served(lw):
            return lw

        trip = jax.jit(ref.int8_round_trip)

        def int8(lw):
            return {k: trip(v) if k in LOSSY[control]
                    else v.astype(jnp.float32) for k, v in lw.items()}

        cfg = ref.whole(self.model)
        if control is None:
            return cfg, (as_served, None)
        if control == "state_bf16":
            return cfg, (as_served, ref.round_bf16)
        if control in LOSSY:
            return cfg, (int8, None)
        raise ValueError(f"unknown control {control!r}")

    def _reference_gaps(self, cfg: dict, how, seqs, firsts, served):
        """Each answer's ``(score gaps, rank gaps)`` against the
        reference's logits at its generated positions (``firsts``: the
        position of the first).

        The reference goes layer by layer (under ``int8_weights`` one
        layer's rounded float32 weights are resident at a time: an ``E``
        layer's 128 held experts, 2.8 GB). A mixer, which mixes a
        sequence's positions (the recurrence token by token, attention
        over every earlier key), takes ``CHUNK`` sequences a call, each
        on its own (``jax.vmap`` of the one-sequence function) and
        right-padded to
        the longest history bucket plus the tokens generated (the model
        is causal: what follows a position does not move it). An ``E``
        layer, which takes every token on its own, takes the REAL tokens
        of all sequences, ``BLOCK`` a call, every held expert applied to
        every one of them. So the check compiles five programs whatever
        the seed sampled."""
        import jax
        import jax.numpy as jnp

        through, round_state = how
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
        pattern = cfg["hybrid_override_pattern"]

        mixers = {}
        for l, letter in enumerate(pattern):
            if letter != "E" and letter not in mixers:
                # the first layer of a letter stands for it
                mixers[letter] = jax.jit(lambda lw, x, l=l: jax.vmap(
                    lambda one: ref.layer(lw, l, one, cfg, round_state))(x))

        @functools.partial(jax.jit, donate_argnums=(1,))
        def experts(lw, flat, at):
            y = ref.layer(lw, pattern.index("E"), flat.at[at].get(
                mode="fill", fill_value=0.0), cfg)
            return flat.at[at].set(y, mode="drop")

        @jax.jit
        def tail(w, x, at, tokens, scores):
            def one(x, at, tokens, scores):
                return ref.served_gaps(ref.head(w, x[at], cfg), tokens,
                                       scores)
            return jax.vmap(one)(x, at, tokens, scores)

        x = ref.embed(top, tokens)
        for l, lw in enumerate(weights["layers"]):
            lw_ = through(lw)
            if pattern[l] == "E":
                flat = x.reshape(N * T, -1)
                del x
                for at in blocks.reshape(-1, BLOCK):
                    flat = experts(lw_, flat, at)
                x = flat.reshape(N, T, -1)
                del flat
            else:
                x = jnp.concatenate([mixers[pattern[l]](lw_, x[c:c + chunk])
                                     for c in range(0, N, chunk)])
            del lw_

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
