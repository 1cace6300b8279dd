"""Generative next-item template: the continuation of a session, item
after item, from a decoder block stack (``models/decoder.py``) driven by
a published language-model configuration, in the same DASE shape as
every shipped template.

Query ``{"items": ["i3", "i9", ...], "num": 32}``; answer
``{"itemScores": [{"item": "i17", "score": 11.2}, ...]}`` with ``num``
entries: entry ``j`` is the ``j``-th item generated (greedily) and its
logit. Item ``"i<k>"`` is token ``k`` of the vocabulary; a history keeps
its last ``max(history_buckets)`` items, and items outside the
vocabulary are dropped.

One query is a prefill and then ``max_new - 1`` decode steps, and a
whole batch of them is ONE ``batch_predict_async``: the rows are padded
to a row bucket and their real tokens packed one row behind the other
(each row ending on a memory tile's edge, ``decoder.row_ends``) into a
stream of ``rows x the history bucket of the batch's MEAN history``
slots, ``_gen_prefill`` and ``_gen_decode`` are enqueued back to back
without a host sync, and the resolver blocks on the answer: the
protocol of ``ALSAlgorithm.batch_predict_async``, so ``StagedPipeline``
serves this engine as it serves ALS, with the next batch's prefill
enqueued behind this one's decode. There is no per-step scheduler, no
paged cache and no prefix reuse (ROADMAP.md).

``train`` materialises the weights from ``params.seed``: importing a
published checkpoint and training this architecture are not in the
repository yet. What persists is the configuration and the seed.
"""

from __future__ import annotations

import functools
import threading
from dataclasses import dataclass, field, replace
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..controller import (
    Algorithm,
    Context,
    DataSource,
    Engine,
    FirstServing,
    IdentityPreparator,
)
from ..utils.tracing import annotate
from .sequential import ItemScore, PredictedResult

IMBALANCE_BOUNDS = (1.0, 1.1, 1.25, 1.5, 2.0, 3.0, 4.0, 8.0, 16.0)
#: |row sum - 1| of a Sinkhorn-normalised mix: float32's rounding at the
#: low end, what a single pass leaves at the high one
SINKHORN_GAP_BOUNDS = (1e-7, 1e-6, 1e-5, 1e-4, 1e-3, 1e-2, 0.1, 1.0)
#: the share of the device's memory that weights and the states of the
#: batches in flight may take; the rest is the prefill's temporaries
RESIDENT_SHARE = 0.8


@dataclass(frozen=True)
class Query:
    items: Tuple[str, ...] = ()
    num: int = 32

    def __post_init__(self):
        object.__setattr__(self, "items", tuple(self.items))


@dataclass(frozen=True)
class GenerativeParams:
    #: the published ``config.json`` keys (``models/decoder.DecoderConfig``)
    model: Dict[str, Any] = field(default_factory=dict)
    seed: int = 0
    #: tokens generated a query (a query's ``num`` is held to it)
    max_new: int = 32
    row_buckets: Tuple[int, ...] = (16, 32, 64)
    history_buckets: Tuple[int, ...] = (128, 256, 512)

    def __post_init__(self):
        object.__setattr__(self, "row_buckets",
                           tuple(sorted(int(b) for b in self.row_buckets)))
        object.__setattr__(
            self, "history_buckets",
            tuple(sorted(int(b) for b in self.history_buckets)))


@dataclass
class GenerativeModel:
    """The configuration, the seed and, once bound, the weights on the
    device."""

    config: Dict[str, Any]
    seed: int = 0
    weights: Optional[dict] = None

    @functools.cached_property
    def cfg(self):
        from ..models.decoder import DecoderConfig

        return DecoderConfig.from_dict(self.config)

    def materialise(self) -> "GenerativeModel":
        if self.weights is not None:
            return self
        import jax

        from ..models.decoder import init_weights

        # the hardware generator: threefry takes 70 s for 4.7 B normals
        # on a v5e (my chip run, PR 27)
        return replace(self, weights=init_weights(
            jax.random.key(self.seed, impl="rbg"), self.cfg))


@dataclass
class TrainingData:
    """Nothing: the vocabulary is the configuration's, the weights the
    seed's."""


@dataclass(frozen=True)
class DataSourceParams:
    app_name: str = ""


class GenerativeDataSource(DataSource):
    def __init__(self, params: DataSourceParams = DataSourceParams()):
        self.params = params

    def read_training(self, ctx: Context) -> TrainingData:
        return TrainingData()


def experts_touched_bounds(num_experts: int) -> Tuple[int, ...]:
    """Bucket bounds of ``pio_moe_experts_touched`` that follow the
    model: powers of two up to an eighth of the experts, eighths from
    there, and the last few one by one (32 experts: 1, 2, 4, 8, 12, ..,
    28, 30, 31, 32)."""
    E = max(int(num_experts), 1)
    low = [b for b in (1, 2, 4, 8, 16, 32, 64) if b < E / 8]
    return tuple(sorted({*low, *(E * i // 8 for i in range(1, 9)),
                         E - E // 16, E - 1, E} - {0}))


def _bucket(buckets: Sequence[int], n: int) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def _state_bytes(cfg, state) -> Dict[str, int]:
    """Bytes of a batch's per-sequence state by kind, from the shapes
    (no sync): ``full``, ``window``, ``conv``, ``latent``, ``ssm`` (a
    state-space layer's recurrent state and its conv window: bytes that
    follow the ROWS, whatever their histories)."""
    from ..models.decoder import ATTENTION, CONV, LATENT, MAMBA, NONE

    out: Dict[str, int] = {}
    for kind, st in zip(cfg.layer_types, state["layers"]):
        if kind == NONE:  # a layer that is its feed-forward alone
            continue
        name = {ATTENTION: "full", CONV: "conv", LATENT: "latent",
                MAMBA: "ssm"}.get(kind, "window")
        out[name] = out.get(name, 0) + sum(a.nbytes for a in st.values())
    return out


class GenerativeAlgorithm(Algorithm):
    """DASE wrapper over ``models/decoder``'s two programs."""

    query_class = Query

    def __init__(self, params: GenerativeParams = GenerativeParams()):
        self.params = params
        self._tokens = self._touched = self._read = self._imbalance = None
        self._assigned = None
        self._state_bytes = self._sinkhorn_gap = None
        self._ssm_bytes = self._ssm_tokens = self._ssm_chunks = None
        # bytes of recurrent state the batches in flight hold (dispatch
        # and readback threads both move it); only a registry reads it
        self._ssm_resident, self._ssm_lock = 0, threading.Lock()

    def train(self, ctx: Context, td: TrainingData) -> GenerativeModel:
        return GenerativeModel(config=dict(self.params.model),
                               seed=self.params.seed).materialise()

    def make_persistent_model(self, model: GenerativeModel,
                              engine_instance_id: str, algo_index: int):
        return replace(model, weights=None)

    def prepare_serving_model(self, model: GenerativeModel,
                              max_batch: int = 1) -> GenerativeModel:
        return model.materialise()

    def register_metrics(self, registry) -> None:
        """The engine's per-batch series on the server's registry
        (docs/observability.md)."""
        self._tokens = registry.counter(
            "pio_gen_tokens_total",
            "Token slots of generative batches by kind: prompt (real "
            "history tokens), pad (the rest of the slots the prefill ran), "
            "generated")
        # the experts HELD here: a chip's share counts its own
        model = self.params.model
        bounds = experts_touched_bounds(
            len(model.get("experts_held") or ()) or model.get(
                "num_experts", model.get("n_routed_experts", 32)))
        self._touched = registry.histogram(
            "pio_moe_experts_touched",
            "Distinct HELD experts a decode step's rows selected, mean "
            "over the expert layers and the steps of a batch (a chip that "
            "holds a share of the experts counts its own)", bounds=bounds)
        self._read = registry.histogram(
            "pio_moe_experts_read",
            "Experts whose weights a decode step fetched, mean over the "
            "expert layers and the steps of a batch: every held expert "
            "where the step took the every-expert product, the held ones "
            "its rows selected where it took another form", bounds=bounds)
        self._imbalance = registry.histogram(
            "pio_moe_load_imbalance",
            "Largest over mean tokens per HELD expert of an expert layer "
            "in a batch's prefill",
            bounds=IMBALANCE_BOUNDS)
        self._assigned = registry.counter(
            "pio_moe_assignments_total",
            "The router's assignments (a token's selected expert, every "
            "expert layer, prefill and decode) by where the expert lives: "
            "held (its weights are on this chip: the product is computed) "
            "or absent (another chip's share: left out here). A prefill "
            "counts its real tokens, a decode step the rows it ran")
        self._state_bytes = registry.gauge(
            "pio_gen_state_bytes",
            "Bytes of per-sequence state the last batch carried from its "
            "prefill to its decode, by kind: full (keys and values that "
            "grow), window (rings of sliding_window), conv (windows of "
            "conv_L_cache), latent (normalised latents beside their rotated "
            "shared key), ssm (state-space layers' recurrent states and conv "
            "windows: they follow the rows, not the histories)")
        self._ssm_bytes = registry.gauge(
            "pio_ssm_state_bytes",
            "Bytes of state-space layers' recurrent state resident on the "
            "device: every batch in flight holds its own from its prefill's "
            "dispatch to its answer's readback")
        self._ssm_tokens = registry.counter(
            "pio_ssm_scan_tokens_total",
            "Real history tokens the prefills' chunked scans ran over (a "
            "layer's scan; pad slots count for nothing)")
        self._ssm_chunks = registry.counter(
            "pio_ssm_scan_chunks_total",
            "Chunks of mamba_chunk_size slots the prefills' scans ran (a "
            "layer's scan): tokens over chunk size x chunks is the share of "
            "a chunk's slots that held a token")
        self._sinkhorn_gap = registry.histogram(
            "pio_mhc_sinkhorn_gap",
            "Largest |row sum - 1| of any hyper-connection mixing matrix "
            "over the real tokens of a batch's prefill: what the Sinkhorn "
            "passes left (models with hc_mult over 1 only)",
            bounds=SINKHORN_GAP_BOUNDS)

    def _history(self, model: GenerativeModel, query: Query) -> List[int]:
        vocab = int(model.config["vocab_size"])
        out = []
        for item in query.items:
            tok = item[1:]
            if item[:1] == "i" and tok.isdigit() and int(tok) < vocab:
                out.append(int(tok))
        return out[-self.params.history_buckets[-1]:]

    def _dispatch(self, model: GenerativeModel, hists: List[List[int]]):
        """Enqueue one packed batch: ``(device outputs, slots)``, the
        slots the prefill ran."""
        return self._enqueue(model, hists)[:2]

    def _enqueue(self, model: GenerativeModel, hists: List[List[int]],
                 sized: bool = False):
        """:meth:`_dispatch` and, third, the bytes of state the batch
        holds on the device by kind (:func:`_state_bytes`): empty unless
        a registry reads them or the caller asks (``sized``)."""
        import jax

        from ..models.decoder import (
            _gen_decode, _gen_prefill, row_align, row_ends)

        p, cfg = self.params, model.cfg
        B = _bucket(p.row_buckets, len(hists))
        lengths = np.ones((B,), np.int32)  # a pad row is one token long
        lengths[:len(hists)] = [len(h) for h in hists]
        # a row ends where the program's tiles do (a few spare slots
        # before its first token), and the stream is sized by the
        # batch's MEAN history so laid out: uniform rows at a history
        # bucket (the warm ladder) give every size there is
        align = row_align(p.history_buckets[-1], cfg.dtype)
        if any(b % align for b in p.history_buckets):
            # the warm ladder would miss the streams such a bucket gives
            raise ValueError(f"history_buckets {p.history_buckets}: each "
                             f"must hold whole tiles of {align} slots")
        ends = row_ends(lengths, align)
        T = B * _bucket(p.history_buckets, -(-int(ends[-1]) // B))
        tokens = np.zeros((T,), np.int32)
        for h, end in zip(hists, ends):
            tokens[end - len(h):end] = h
        # explicit: the server's transfer guard logs an implicit one
        tokens, lengths = jax.device_put((tokens, lengths))
        with annotate("pio:gen_prefill", rows=B, slots=T):
            first, state = _gen_prefill(
                model.weights, tokens, lengths, cfg=cfg,
                history=p.history_buckets[-1], room=p.max_new)
        held: Dict[str, int] = {}
        if sized or self._state_bytes is not None:
            held = _state_bytes(cfg, state)  # once a batch, from shapes
        if self._state_bytes is not None:
            for kind, nbytes in held.items():
                self._state_bytes.labels(kind=kind).set(nbytes)
        # a scalar beside the state, not of it: the decode is not given
        # it, and it rides behind the answer's arrays where there is one
        gap = state.pop("sinkhorn_gap", None)
        with annotate("pio:gen_decode", rows=B, steps=p.max_new):
            toks, scores, load, _ = _gen_decode(
                model.weights, state, first, cfg=cfg, steps=p.max_new)
        return ((toks, scores, load) + (() if gap is None else (gap,)), T,
                held)

    def _ssm_moves(self, nbytes: int) -> None:
        """A served batch's recurrent state arrives on the device
        (``nbytes`` over 0) or leaves it with its answer."""
        if not nbytes:
            return  # no state-space layers, or no registry: no series
        with self._ssm_lock:
            self._ssm_resident += nbytes
            self._ssm_bytes.set(self._ssm_resident)

    def _observe(self, cfg, hists, rows: int, slots: int, load,
                 gap=None) -> None:
        """Once a batch, never per query: its queries' histories, the
        ``rows`` its decode ran, the ``slots`` its prefill ran and the
        ``gap`` its Sinkhorn passes left (``None``: one residual
        stream)."""
        if self._tokens is None:
            return
        from ..ops import moe

        if gap is not None:
            self._sinkhorn_gap.observe(float(gap))

        prompt = sum(len(h) for h in hists)
        if cfg.mamba_n_heads:  # a layer's scan: its tokens, its chunks
            self._ssm_tokens.inc(prompt)
            self._ssm_chunks.inc(-(-slots // cfg.mamba_chunk_size))
        self._tokens.labels(kind="prompt").inc(prompt)
        self._tokens.labels(kind="pad").inc(slots - prompt)
        self._tokens.labels(kind="generated").inc(
            len(hists) * self.params.max_new)
        prefill, decode = (np.asarray(a) for a in load)
        # the loads are over ALL the router's experts; a chip's own
        # series count the experts it holds
        held = slice(None) if cfg.experts_held is None \
            else list(cfg.experts_held)
        mine, steps = prefill[:, held], decode[..., held]
        if decode.size:
            # from the loads the program returns anyway and the form
            # ops/moe.py takes for a step of that many rows: no sync
            n_held = steps.shape[-1]
            touched = float((steps > 0).sum(axis=-1).mean())
            self._touched.observe(touched)
            every = moe.product_form(rows, cfg.num_experts_per_tok, n_held,
                                     cfg.num_experts) == moe.EVERY
            self._read.observe(float(n_held) if every else touched)
        for layer in mine:
            if layer.sum() > 0:
                self._imbalance.observe(float(layer.max() / layer.mean()))
        if prefill.size:
            here = int(mine.sum() + steps.sum())
            self._assigned.labels(where="held").inc(here)
            self._assigned.labels(where="absent").inc(
                int(prefill.sum() + decode.sum()) - here)

    def warm_serving(self, model: GenerativeModel,
                     max_batch: int = 1) -> None:
        """Compile the ladder: every row bucket up to ``max_batch``'s
        with uniform rows at every history bucket, which is every
        stream size a batch of that many rows can have (a prefill each;
        the decode's program depends on the rows alone)."""
        p = self.params
        top = _bucket(p.row_buckets, max(max_batch, 1))
        for b in p.row_buckets:
            if b > top:
                break
            for L in p.history_buckets:
                arrays, _, held = self._enqueue(model, [[0] * L] * b,
                                                sized=True)
                arrays[0].block_until_ready()
        self._check_residency(model, sum(held.values()))

    def _check_residency(self, model: GenerativeModel, state: int) -> None:
        """Weights and ``batches_in_flight`` batches' ``state`` bytes (a
        batch of the ladder's top: what the last warm dispatch held)
        against the device's memory, where the backend reports one. The
        weights are what this chip HOLDS (a share of the experts, a
        slice of the vocabulary: ``experts_held`` and ``vocab_size`` of
        the configuration). A state-space model's state follows the rows
        and not the histories (2 or 4 MiB a row and layer at the
        published sizes), so it is the rows
        a batch may have, and not the cache a history needs, that a
        deployment sizes here: a ladder that does not fit fails the
        deploy, before traffic finds out."""
        import jax

        leaves = jax.tree_util.tree_leaves(model.weights)
        limit = (leaves[0].devices().pop().memory_stats() or {}).get(
            "bytes_limit") if leaves else None
        if not limit:
            return
        resident = sum(a.nbytes for a in leaves) \
            + self.batches_in_flight * state
        if resident > RESIDENT_SHARE * limit:
            raise RuntimeError(
                f"weights and {self.batches_in_flight} batches' state are "
                f"{resident / 1e9:.2f} GB of the device's "
                f"{limit / 1e9:.2f}: lower row_buckets, history_buckets or "
                f"the server's pipeline_depth")

    def batch_predict_async(self, model: GenerativeModel,
                            queries: Sequence[Query]):
        """Dispatch half of :meth:`batch_predict`: enqueues the prefill
        and the decode of every row-bucketful of queries and returns a
        no-arg resolver that blocks on the device arrays and builds the
        per-query results."""
        import jax

        hists = [self._history(model, q) for q in queries]
        live = [i for i, h in enumerate(hists) if h]
        out: List[PredictedResult] = [PredictedResult()] * len(queries)
        top = self.params.row_buckets[-1]
        chunks = [live[s:s + top] for s in range(0, len(live), top)]
        pending = []
        for chunk in chunks:
            pending.append((chunk,) + self._enqueue(
                model, [hists[i] for i in chunk]))
            # in flight from here to its readback, its state with it
            self._ssm_moves(pending[-1][-1].get("ssm", 0))

        def resolve() -> List[PredictedResult]:
            for chunk, arrays, slots, held in pending:
                toks, scores, load, *gap = jax.device_get(arrays)
                self._ssm_moves(-held.get("ssm", 0))
                self._observe(model.cfg, [hists[i] for i in chunk],
                              len(toks), slots, load, *gap)
                for row, i in enumerate(chunk):
                    n = min(max(queries[i].num, 0), self.params.max_new)
                    out[i] = PredictedResult(tuple(
                        ItemScore(item=f"i{int(t)}", score=float(s))
                        for t, s in zip(toks[row, :n], scores[row, :n])))
            return out

        return resolve

    def batch_predict(self, model: GenerativeModel,
                      queries: Sequence[Query]) -> List[PredictedResult]:
        """Dispatch + immediate readback of
        :meth:`batch_predict_async`: the two never diverge."""
        return self.batch_predict_async(model, queries)()

    def predict(self, model: GenerativeModel,
                query: Query) -> PredictedResult:
        return self.batch_predict(model, [query])[0]


class GenerativeServing(FirstServing):
    pass


def generative_engine() -> Engine:
    """Engine factory."""
    return Engine(
        datasource_classes=GenerativeDataSource,
        preparator_classes=IdentityPreparator,
        algorithm_classes={"decoder": GenerativeAlgorithm,
                           "": GenerativeAlgorithm},
        serving_classes=GenerativeServing,
        datasource_params_class=DataSourceParams,
        algorithm_params_classes={"decoder": GenerativeParams,
                                  "": GenerativeParams},
    )
