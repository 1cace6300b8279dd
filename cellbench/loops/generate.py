"""The ``generate`` loop: the generative template's engine
(``templates/generative.py``) bound into a real ``QueryServer`` over
HTTP in this process, which holds the chip, under the closed loop of
``serve.py``'s load generators. A query is a history of items and
``num``; an answer is the ``num`` items generated and their logits.

What it shares with ``serve.py`` (imported, not edited): the children
(``loadgen.py``), the digest of their reports into the end-to-end
numbers and the ``loadgen`` facts, ``end_to_end``, ``traced_rates`` and
``close``. Its own: the inputs (weights, histories), the engine it
binds, the shapes it warms, the traced window (a dispatch here lasts
most of a second, so the capture's cut edges are taken off: see
``whole_dispatches``) and ``check`` against ``reference_lfm2.py``.
"""

from __future__ import annotations

import functools
import json
import multiprocessing
import time
from types import SimpleNamespace

import numpy as np

from .. import data, loadgen, readers, reference_lfm2 as ref, trace
from . import serve

#: the keys of a configuration file that are the model's own
NOT_MODEL = ("name", "source", "system", "precision", "published",
             "deployment", "assumed", "init", "server", "engine", "check",
             "rehearse")


def model_keys(config: dict) -> dict:
    return {k: v for k, v in config.items() if k not in NOT_MODEL}


#: what the reference takes in one call: sequences through a layer's
#: operator, tokens through its feed-forward
CHUNK, BLOCK = 8, 2048


def history_lengths(tr: dict, n: int) -> np.ndarray:
    """The traffic's multiset of history lengths: lognormal, clipped,
    from the traffic's OWN seed, so that every run's seed sees the same
    lengths in another order (and the same shapes and bytes)."""
    h = tr["history"]
    rng = np.random.default_rng([int(h["data_seed"]), 0x6e])
    raw = rng.lognormal(np.log(float(h["median"])), float(h["sigma"]), n)
    return np.clip(np.rint(raw), int(h["min"]), int(h["max"])).astype(int)


def whole_dispatches(events, window_s: float):
    """The capture less its cut edges. A dispatch that began before the
    capture (or ends after it) leaves operations that belong to no
    ``XLA Modules`` event, or to one cut short: on every device plane
    keep the module events but the first and the last, the operations
    inside the kept ones, and take as the window the stretch from the
    first kept module's start to the last one's end. Returns ``(events,
    window_s, cut_s)``; the capture as it is where a plane holds fewer
    than three modules."""
    planes = sorted({e[0] for e in events if e[0].startswith("/device:")})
    kept, spans = [e for e in events if not e[0].startswith("/device:")], []
    for plane in planes:
        mods = sorted((e for e in events
                       if e[0] == plane and e[1] == "XLA Modules"),
                      key=lambda e: e[3])
        if len(mods) < 3:
            return events, window_s, 0.0
        mods = mods[1:-1]
        lo, hi = mods[0][3], max(m[3] + m[4] for m in mods)
        spans.append((lo, hi))
        starts = np.array([m[3] for m in mods], dtype=np.int64)
        ends = np.array([m[3] + m[4] for m in mods], dtype=np.int64)
        kept += mods
        for e in events:
            if e[0] == plane and e[1] == "XLA Ops":
                i = int(np.searchsorted(starts, e[3], side="right")) - 1
                if i >= 0 and e[3] + e[4] <= ends[i]:
                    kept.append(e)
    whole = max(hi - lo for lo, hi in spans) / 1e9
    return kept, whole, window_s - whole


class Cell(serve.Cell):
    def __init__(self, **kw):
        super().__init__(**kw)
        self.open = False
        # serve._digest's ALS shapes; nothing in this cell reads them
        self.bound = SimpleNamespace(item_factors=SimpleNamespace(nbytes=0))
        self.n_items = self.rank = 0

    # -- inputs: what the system would be given ---------------------------
    def inputs(self) -> None:
        import jax

        from predictionio_tpu.models import decoder

        tr = self.traffic
        self.model = model_keys(self.config)
        self.cfg = decoder.DecoderConfig.from_dict(self.model)
        # the device's own generator: threefry takes 70 s for these
        # 4.7 B normals (my chip run, PR 27)
        key = jax.random.fold_in(
            jax.random.key(self.seed >> 31, impl="rbg"),
            self.seed & 0x7fffffff)
        self.weights = jax.block_until_ready(
            decoder.init_weights(key, self.cfg, self.config["init"]))

        G, num = int(tr["generators"]), int(tr["num"])
        n = G * int(np.ceil(float(tr["max_qps"]) * self.seconds / G))
        rng = np.random.default_rng([self.seed, 0x9e4])
        lengths = rng.permutation(history_lengths(tr, n))
        tokens = data.sample_entities(rng, self.cfg.vocab_size,
                                      int(lengths.sum()), tr.get("zipf"))
        ends = np.cumsum(lengths)
        self.histories = [tokens[e - k:e].tolist()
                          for e, k in zip(ends, lengths)]
        self.lengths = lengths
        sure = min(n, int(float(tr["min_qps"]) * self.seconds))
        self.keep = sorted(rng.choice(
            sure, min(int(tr["check_sample"]), sure),
            replace=False).tolist())
        keep = set(self.keep)
        ctx = multiprocessing.get_context("spawn")
        for c in range(G):
            mine = range(c, n, G)
            spec = {"connections": int(tr["connections"]), "num": num,
                    "bodies": [json.dumps(
                        {"items": [f"i{t}" for t in self.histories[g]],
                         "num": num}).encode() for g in mine],
                    "keep": [g // G for g in mine if g in keep]}
            here, there = ctx.Pipe()
            proc = ctx.Process(target=loadgen.child_main,
                               args=(there, spec), daemon=True)
            proc.start()
            there.close()
            self.children.append((proc, here))

    # -- set-up: everything the system does before the window -------------
    def _boot(self, model):
        from datetime import datetime, timezone

        from predictionio_tpu.controller import Context
        from predictionio_tpu.controller.params import EngineParams
        from predictionio_tpu.data.storage import App, Storage
        from predictionio_tpu.data.storage.base import (
            STATUS_COMPLETED, EngineInstance)
        from predictionio_tpu.server.engineserver import (
            QueryServer, ServerConfig, create_engine_server)
        from predictionio_tpu.templates.generative import (
            GenerativeParams, generative_engine)

        storage = Storage(env={"PIO_STORAGE_SOURCES_MEM_TYPE": "memory"})
        storage.apps().insert(App(0, "cellbench"))
        ctx = Context(app_name="cellbench", _storage=storage)
        now = datetime.now(timezone.utc)
        inst = EngineInstance(
            id="cellbench", status=STATUS_COMPLETED, start_time=now,
            end_time=now, engine_id="cellbench", engine_version="1",
            engine_variant="engine.json", engine_factory="synthetic")
        params = GenerativeParams(
            model=self.model, max_new=int(self.traffic["num"]),
            **self.config["engine"])
        # the benchmark warms the shapes the traffic lists itself
        cfg = ServerConfig(**self.config["server"], warm_start=False)
        qs = QueryServer(
            ctx, generative_engine(),
            EngineParams(algorithms=(("decoder", params),)),
            [model], inst, cfg)
        srv = create_engine_server(qs, host="127.0.0.1", port=0)
        srv.start_background()
        return qs, srv

    def setup(self) -> None:
        from predictionio_tpu.templates.generative import GenerativeModel

        model = GenerativeModel(config=self.model, seed=self.seed,
                                weights=self.weights)
        with self.stamps.stage("bind"):
            self.qs, self.srv = self._boot(model)
        with self.stamps.stage("warm"):
            algo, bound = self.qs.algorithms[0], self.qs.models[0]
            for rows, history in self.traffic["warm_shapes"]:
                arrays, _ = algo._dispatch(bound,
                                           [[0] * int(history)] * int(rows))
                arrays[0].block_until_ready()
            self.qs.recompile_sentinel.arm()
        for _, pipe in self.children:
            pipe.send(self.srv.port)
        for _, pipe in self.children:
            if not pipe.poll(60) or pipe.recv() != "ready":
                raise RuntimeError("a load generator did not connect")

    # -- the timed window ---------------------------------------------------
    def window(self) -> None:
        T = self.seconds
        before = self.qs.metrics.export()
        t0 = time.monotonic() + 0.25
        for _, pipe in self.children:
            pipe.send((t0, T))
        clean_until = T
        spans = {}
        if self.traced:
            plan = self.traffic["trace"]
            clean_until = max(T - float(plan["before_end_s"]), 0.4 * T)
            time.sleep(max(t0 + clean_until - time.monotonic(), 0))
            after = self.qs.metrics.export()
            a0 = time.monotonic() - t0
            with trace.Capture(python=False) as cap:
                time.sleep(float(plan["device_s"]))
            spans["python_off"] = (a0, a0 + cap.window_s)
            on_chip = self.devs[0].platform != "cpu"
            if on_chip:  # a rehearsal's CPU trace has no device plane
                events, whole_s, cut_s = whole_dispatches(cap.events,
                                                          cap.window_s)
                self.say("trace_cut", {"captured_s": cap.window_s,
                                       "kept_s": whole_s, "cut_s": cut_s})
                self.facts["trace"] = trace.reduce_device(events, whole_s)
                ops = trace.top_device_ops(self.facts["trace"])
            b0 = time.monotonic() - t0
            with trace.Capture(python=True) as cap:
                time.sleep(float(plan["host_s"]))
            spans["python_on"] = (b0, b0 + cap.window_s)
            if on_chip:
                self.facts["breakdown"] = {
                    "device_ops": ops,
                    "idle_gaps": trace.idle_gaps(cap.events)}
        time.sleep(max(t0 + T - time.monotonic(), 0))
        if not self.traced:
            after = self.qs.metrics.export()
        results = []
        for proc, pipe in self.children:
            if not pipe.poll(T + 120):
                raise RuntimeError("a load generator did not report")
            results.append(pipe.recv())
            proc.join(30)
        self.compiles = int(self.qs.recompile_sentinel.since_armed)
        self.facts["registry"] = (before, after)
        self._digest(results, clean_until, spans)
        self._shapes()

    def _digest(self, results, clean_until: float, spans: dict) -> None:
        """``serve.py``'s digest, and the clients' MEAN latency over the
        same part of the window: an answer here takes seconds, where
        the server's histograms are too coarse for a p50, so this
        cell's latency metrics read means on both sides."""
        super()._digest(results, clean_until, spans)
        lat, done = (np.array([v for r in results
                               for v, w in zip(r[k], r["lat"])
                               if w is not None])
                     for k in ("lat", "done"))
        mine = (lat >= 0) & (done - lat < clean_until)
        if mine.any():
            self.facts["loadgen"]["lat_mean_ms"] = float(
                lat[mine].mean() * 1e3)

    def _shapes(self) -> None:
        """What the roofline readers need: the batch the window ran at,
        the experts its decode steps touched, its histories' sizes."""
        def series(metric):
            return readers.read(self.facts, {
                "reader": "registry", "metric": metric, "stat": "mean"})

        rows = float(series("pio_batch_occupancy") or 0.0)
        touched = series("pio_moe_experts_touched")
        if not rows or touched is None:
            return  # a program from before the engine: nothing to read
        lengths = self.lengths.astype(np.float64)
        steps = int(self.traffic["num"]) - 1
        self.facts["shapes"] = {
            "gen_decode": {
                "cfg": self.model, "rows": rows, "steps": steps,
                "experts_touched": float(touched),
                "history_mean": float(lengths.mean())},
            "gen_prefill": {
                "cfg": self.model, "rows": rows,
                "tokens": rows * float(lengths.mean()),
                "tokens_squared": rows * float((lengths ** 2).mean())}}
        self.say("shapes", {k: {a: b for a, b in v.items() if a != "cfg"}
                            for k, v in self.facts["shapes"].items()})

    # -- the output check, outside every clock ------------------------------
    def _reference_gaps(self, cfg: dict, widen, seqs, firsts, served):
        """Each answer's ``(score gaps, rank gaps)`` against the
        reference's logits at its generated positions (``firsts``: the
        position of the first).

        The reference goes layer by layer, so that one layer's float32
        weights (``widen(layer)``: 1.4 GB of experts) are resident at a
        time. A layer's operator, which mixes a sequence's positions,
        takes ``CHUNK`` sequences a call, each on its own (``jax.vmap``
        of the one-sequence function) and right-padded to the longest
        history bucket (the model is causal: what follows a position
        does not move it). Its feed-forward, which takes every token on
        its own and is 19 of 20 of the work, takes the REAL tokens of all
        sequences, ``BLOCK`` a call. So the check compiles five programs
        whatever the seed sampled, and pays for no padding where the
        work is."""
        import jax
        import jax.numpy as jnp

        weights, n = self.weights, int(self.traffic["num"])
        e32 = {"embed": weights["embed"].astype(jnp.float32),
               "norm_out": weights["norm_out"]}
        T = max(int(b) for b in
                self.config["engine"]["history_buckets"]) + n
        N = -(-len(seqs) // CHUNK) * CHUNK
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
        blocks = blocks.reshape(-1, BLOCK)

        ops, ffs = {}, {}
        for l, lw in enumerate(weights["layers"]):
            kind, dense = cfg["layer_types"][l], \
                l < int(cfg["num_dense_layers"])
            if kind not in ops:
                ops[kind] = jax.jit(lambda lw, x, l=l: jax.vmap(
                    lambda one: ref.operator(lw, l, one, cfg))(x))
            if dense not in ffs:
                @functools.partial(jax.jit, donate_argnums=(1,))
                def ff(lw, flat, at, l=l):
                    y = ref.feed_forward(lw, l, flat.at[at].get(
                        mode="fill", fill_value=0.0), cfg)
                    return flat.at[at].set(y, mode="drop")
                ffs[dense] = ff

        x = ref.embed(e32, tokens)
        for l, lw in enumerate(weights["layers"]):
            lw32 = widen(lw)
            op = ops[cfg["layer_types"][l]]
            flat = jnp.concatenate(
                [op(lw32, x[c:c + CHUNK]) for c in range(0, N, CHUNK)]
            ).reshape(N * T, -1)
            del x
            for at in blocks:
                flat = ffs[l < int(cfg["num_dense_layers"])](lw32, flat, at)
            x = flat.reshape(N, T, -1)
            del lw32, flat

        @jax.jit
        def tail(w, x, at, tokens, scores):
            def one(x, at, tokens, scores):
                return ref.served_gaps(ref.head(w, x[at], cfg), tokens,
                                       scores)
            return jax.vmap(one)(x, at, tokens, scores)

        out = []
        for c in range(0, N, CHUNK):
            rows = [min(i, len(seqs) - 1) for i in range(c, c + CHUNK)]
            at = np.array([firsts[i] for i in rows])[:, None] + np.arange(n)
            s, r = tail(e32, x[c:c + CHUNK], at,
                        np.stack([served[i][0] for i in rows]),
                        np.stack([served[i][1] for i in rows]
                                 ).astype(np.float32))
            out += list(zip(np.asarray(s, np.float64),
                            np.asarray(r, np.float64)))
        return out[:len(seqs)]

    def _reference_under(self, control):
        """``(cfg, widen)`` of the reference, sound (``None``) or under
        a control one step below the configuration."""
        import jax
        import jax.numpy as jnp

        def widen(lw):
            return jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32), lw)

        def int8_experts(lw):
            wide = widen(lw)
            if "gate" in lw:  # an expert layer
                wide.update({k: ref.int8_round_trip(lw[k])
                             for k in ("w1", "w3", "w2")})
            return wide

        if control is None:
            return self.model, widen
        if control == "no_topk_norm":
            return {**self.model, "norm_topk_prob": False}, widen
        if control == "int8_experts":
            return self.model, int8_experts
        raise ValueError(f"unknown control {control!r}")

    def _compare(self, parsed: dict, control=None) -> dict:
        """The served answers against the reference, position by
        position, in units of a position's spread of reference logits
        (``reference_lfm2.served_gaps``). The limits sit on medians,
        because a single position is no rounding error: a top-4
        membership that the served path's bfloat16 operands decide the
        other way (the 4th and 5th scores of 32 lie closer than the
        rounding at a few per cent of tokens and layers) replaces a
        quarter of an expert block's output, the layers after it then
        route differently too, and about a quarter of all positions end
        a spread or more from the reference (PERF.md finding 27.2). So:
        ``score_gap_p50``, pooled over all positions, is where a lower
        precision everywhere shows; ``*_gap_max`` is the worst ANSWER's
        median over its positions, where a row gone wrong shows. The
        tails are printed beside them."""
        t0 = time.perf_counter()
        good = sorted(parsed)
        seqs = [self.histories[g] + parsed[g][0][:-1].tolist()
                for g in good]
        firsts = [len(self.histories[g]) - 1 for g in good]
        gaps = self._reference_gaps(*self._reference_under(control), seqs,
                                    firsts, [parsed[g] for g in good])
        score = np.stack([s for s, _ in gaps])
        rank = np.stack([r for _, r in gaps])
        seconds = time.perf_counter() - t0
        return {"score_gap_p50": float(np.median(score)),
                "score_gap_max": float(np.median(score, axis=1).max()),
                "rank_gap_max": float(np.median(rank, axis=1).max()),
                "score_gap_p90": float(np.percentile(score, 90)),
                "score_gap_p99": float(np.percentile(score, 99)),
                "score_gap_worst": float(score.max()),
                "rank_gap_p90": float(np.percentile(rank, 90)),
                "rank_gap_p99": float(np.percentile(rank, 99)),
                "rank_gap_worst": float(rank.max()),
                "greedy_agrees_share": float((rank <= 0).mean()),
                "seconds": seconds,
                "seconds_per_answer": seconds / max(len(good), 1)}

    def check(self) -> list:
        lim = self.config["check"]
        k, vocab = int(self.traffic["num"]), self.cfg.vocab_size
        parsed, malformed = {}, 0
        for g in sorted(self.answers):
            try:
                got = json.loads(self.answers[g])["itemScores"]
                ids = np.array([int(x["item"][1:]) for x in got])
                vals = np.array([float(x["score"]) for x in got])
                if len(ids) != k or ids.min() < 0 or ids.max() >= vocab:
                    raise ValueError("ids out of range")
                parsed[g] = (ids, vals)
            except (KeyError, ValueError, TypeError):
                malformed += 1
        self.failed += malformed
        worst = float("inf")
        # ``check.control`` is null in the file: ``control_gen.py`` sets
        # it for a run that has to come out NOT correct
        control = lim.get("control")
        read = self._compare(parsed, control) if parsed else {}
        self.say("check_detail", {"answers_compared": len(parsed),
                                  "malformed": malformed,
                                  "control": control, **read})
        return [
            {"name": "score_gap_max", "limit": lim["score_gap_max"],
             "value": read.get("score_gap_max", worst)},
            {"name": "rank_gap_max", "limit": lim["rank_gap_max"],
             "value": read.get("rank_gap_max", worst)},
            {"name": "score_gap_p50", "limit": lim["score_gap_p50"],
             "value": read.get("score_gap_p50", worst)},
            {"name": "answers_not_compared", "limit": 0,
             "value": len(self.keep) - len(parsed)},
            {"name": "failed_requests", "limit": 0, "value": self.failed},
            {"name": "compiles_in_window", "limit": 0,
             "value": self.compiles},
        ]
