"""A config-driven decoder block stack with generation: short-convolution
and grouped-query attention layers side by side, dense and sparse-expert
feed-forwards, driven by the keys of a published ``config.json`` (the
``lfm2_moe`` family's names).

Two jitted entry points, whose names the benchmark's metrics match in
the device trace: :func:`_gen_prefill` runs the histories of a batch and
leaves the per-sequence state on the device; :func:`_gen_decode` takes
that state (donated) and generates greedily, ``steps - 1`` forward
passes in one program (the first token is the prefill's). The host
sees one dispatch of two programs and syncs once, on the answer.

Layer ``l``: ``h = x + op_l(n(x))``, ``y = h + ff_l(n(h))`` with RMSNorm
``n``; ``op_l`` by ``layer_types[l]`` (``conv`` or ``full_attention``),
``ff_l`` dense for ``l < num_dense_layers`` and the expert block
(``ops/moe.py``) after. ``models/decoder_reference.py`` writes the
equations out; the tests hold this module to it logit by logit.

Layout. The prefill takes a batch PACKED: the real tokens of its rows
one behind the other in one stream of ``T`` slots (``tokens [T]``,
``lengths [B]``; the spare slots behind the last row hold anything), so
its cost follows the tokens a batch has and not rows x its longest row.
Whatever treats a token on its own (norms, projections, feed-forwards,
the router, the expert products) runs over ``[T, H]`` and knows no rows.
The two operators that mix positions stay inside a row: a conv tap that
would reach before a row's first token adds zero, and attention runs
over the rows gathered into the right-aligned ``[B, history]`` layout
that the decode's cache has anyway (pad slots masked by ``key_valid``),
with rotary positions counted from a row's first token. A spare slot
joins no expert's group and no row reads it: a row's logits do not
depend on where in the stream it lies or on what lies beside it. State
of two kinds is carried from one program to the next: keys and values
that grow (attention layers), right-aligned at ``history`` slots
whatever the batch so that every row appends at the same slot and the
decode's shapes depend on ``B`` alone, and a fixed ``conv_L_cache``-wide
window of ``B*u`` (conv layers).

Precision. Weights in ``cfg.dtype`` (bfloat16 as served). Every matrix
product takes operands in that dtype and accumulates in float32
(``preferred_element_type``); the residual stream, norms, rotary,
softmax, the gate's sigmoid and sums, and the conv window are float32.

Every layer holds its own arrays and the stack is unrolled. (Stacking
the periods of the layer pattern and scanning them compiles the period
once, but the compiler then materialises each scanned slice of a stack
that a custom call reads: a 704 MB copy of one layer's experts a layer
and a step, read off the compiled program, which would triple what a
decode step moves.)
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, fields
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import moe
from ..ops.ring_attention import ring_attention

CONV, ATTENTION = "conv", "full_attention"
#: rows whose attention is taken in one call: a row group's scores are
#: ``[rows, heads, history, history]`` float32 (0.5 GB at 16 x 32 x 512
#: x 512), and the group bounds what the program holds beside the
#: stream: 1.55 GB of temporaries at 16,384 slots where the 64 rows
#: whole take 2.89 (compiled for the v5e). The prefill of 64 rows in
#: 16,384 slots took 466.8 ms in groups of 8, 466.4 at 16, 469.4 at 32
#: and 470.3 whole (my chip run, PR 28)
ATTENTION_ROWS = 16


@dataclass(frozen=True)
class DecoderConfig:
    """The published keys, by their published names, plus ``dtype`` and
    ``experts_held`` (which experts' weights this chip holds; ``None``:
    all)."""

    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_hidden_layers: int
    layer_types: Tuple[str, ...]
    num_dense_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    num_experts: int
    num_experts_per_tok: int
    vocab_size: int
    conv_L_cache: int = 3
    conv_bias: bool = False
    norm_eps: float = 1e-5
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    rope_theta: float = 1e6
    head_dim: Optional[int] = None
    dtype: str = "bfloat16"
    experts_held: Optional[Tuple[int, ...]] = None

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.experts_held is not None:
            object.__setattr__(self, "experts_held",
                               tuple(int(e) for e in self.experts_held))
        if self.head_dim is None:
            object.__setattr__(
                self, "head_dim",
                self.hidden_size // self.num_attention_heads)
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError("layer_types must name num_hidden_layers "
                             "layers")
        if set(self.layer_types) - {CONV, ATTENTION}:
            raise ValueError(f"layer types {set(self.layer_types)}: only "
                             f"{CONV!r} and {ATTENTION!r} are written")
        if self.conv_bias:
            raise ValueError("conv_bias: the family publishes none")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must divide by key-value heads")

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "DecoderConfig":
        """From a ``config.json``'s dict; keys that say nothing about
        the block (``model_type``, ``max_position_embeddings``, the
        benchmark's own notes) are left where they are."""
        names = {f.name for f in fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})

    @property
    def n_held(self) -> int:
        return self.num_experts if self.experts_held is None \
            else len(self.experts_held)


# -- weights ----------------------------------------------------------------

#: scales of the seeded weights (nothing is trained or imported here).
#: ``embed``: the embedding's standard deviation. Every matrix is normal
#: x 1/sqrt(fan-in), which maps a normalised input to an output of
#: about unit RMS; the matrices that write to the residual stream take
#: one more factor from LAYER 1 ON: ``op_out`` (a conv layer's
#: ``w_out``, an attention layer's ``wo``), ``dense_out`` and
#: ``expert_out`` (``w2``). Layer 0 stays at 1: it, and not the tied
#: embedding, is the stream the later layers add to (were the embedding
#: the stream, a token's own logit would stand 45 spreads above the
#: rest and greedy would repeat it). With every factor at 1 each
#: sublayer adds as much as the one before and every gated product
#: doubles what was rounded off earlier: bfloat16 operands then read
#: 6-9 % of a logit's spread after 14 layers and the experts carry a
#: tenth of the residual (my chip runs, PR 27). At these factors an
#: operator adds 0.08, a dense feed-forward 0.4 and an expert block 0.6
#: of layer 0's RMS: the stream grows slowly (1.1 to 2.4 over the 14
#: layers of the benchmark's cut), the gated operators amplify little,
#: and the expert blocks carry most of what is added.
INIT = {"embed": 0.02, "op_out": 0.08, "dense_out": 0.67,
        "expert_out": 2.0, "gate_bias": 0.01}


def _layer_shapes(cfg: DecoderConfig, kind: str, dense: bool) -> dict:
    """``{name: (shape, fan_in, factor)}`` of one layer; fan-in 0 marks
    a gain (ones); ``factor`` names the entry of :data:`INIT` a matrix
    is scaled by beside 1/sqrt(fan-in) (``None``: none)."""
    H, D = cfg.hidden_size, cfg.head_dim
    nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    out = {"op_norm": ((H,), 0, None), "ff_norm": ((H,), 0, None)}
    if kind == CONV:
        out.update(w_in=((H, 3 * H), H, None),
                   w_out=((H, H), H, "op_out"),
                   conv_w=((H, cfg.conv_L_cache), cfg.conv_L_cache, None))
    else:
        out.update(wq=((H, nq * D), H, None), wk=((H, nkv * D), H, None),
                   wv=((H, nkv * D), H, None),
                   wo=((nq * D, H), nq * D, "op_out"),
                   q_norm=((D,), 0, None), k_norm=((D,), 0, None))
    if dense:
        I = cfg.intermediate_size
        out.update(w1=((H, I), H, None), w3=((H, I), H, None),
                   w2=((I, H), I, "dense_out"))
    else:
        E, F = cfg.n_held, cfg.moe_intermediate_size
        out.update(gate=((H, cfg.num_experts), H, None),
                   w1=((E, H, F), H, None), w3=((E, H, F), H, None),
                   w2=((E, F, H), F, "expert_out"))
        if cfg.use_expert_bias:
            out["gate_bias"] = ((cfg.num_experts,), 1, "gate_bias")
    return out


@functools.partial(jax.jit, static_argnames=("shapes", "dtype"))
def _draw(key, init: dict, *, shapes: tuple, dtype: str) -> dict:
    """One layer's arrays; jitted per signature of shapes, so a stack
    compiles three small programs and not one of every layer."""
    out = {}
    for i, (name, (shape, fan, factor)) in enumerate(shapes):
        k = jax.random.fold_in(key, i)
        if fan == 0:
            out[name] = jnp.ones(shape, jnp.float32)
            continue
        scale = jnp.asarray(fan ** -0.5, jnp.float32)
        if factor is not None:
            scale = scale * init[factor]
        # drawn in the target dtype: a float32 draw of one layer's
        # experts would be 1.4 GB of scratch beside 9 GB of weights
        kind = jnp.float32 if name in ("conv_w", "gate_bias") else dtype
        out[name] = jax.random.normal(k, shape, kind) * scale.astype(kind)
    return out


def init_weights(key: jax.Array, cfg: DecoderConfig,
                 init: Optional[Dict[str, float]] = None) -> dict:
    """Seeded weights on the device: matrices normal / sqrt(fan-in)
    times their factor of :data:`INIT` (``init`` overrides entries of
    it; they are traced, so another scale is not another program),
    embedding normal x ``embed`` (tied to the head), gains 1, the expert
    bias normal x ``gate_bias``. ``{"embed", "norm_out", "layers":
    [layer, ...]}``: the tree ``decoder_reference`` reads too."""
    init = {**INIT, **(init or {})}
    unit = {**init, "op_out": 1.0, "dense_out": 1.0, "expert_out": 1.0}
    ke, kl = jax.random.split(key)
    top = (("embed", ((cfg.vocab_size, cfg.hidden_size), 1, "embed")),
           ("norm_out", ((cfg.hidden_size,), 0, None)))
    return {
        **_draw(ke, init, shapes=top, dtype=cfg.dtype),
        "layers": [
            _draw(jax.random.fold_in(kl, l), init if l else unit,
                  shapes=tuple(sorted(_layer_shapes(
                      cfg, kind, l < cfg.num_dense_layers).items())),
                  dtype=cfg.dtype)
            for l, kind in enumerate(cfg.layer_types)]}


# -- pieces -----------------------------------------------------------------

def _rms(x, gain, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def _dot(a, w):
    return jnp.dot(a.astype(w.dtype), w,
                   preferred_element_type=jnp.float32)


def _rotary(x, pos, theta):
    """Rotate-half rotary over the whole head: ``x [..., heads, D]``,
    ``pos`` shaped like ``x`` without its last two axes."""
    D = x.shape[-1]
    # ptpu: allow[unguarded-domain] — D is the static head size, never 0
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = pos.astype(jnp.float32)[..., None, None] * inv
    ang = jnp.concatenate([ang, ang], axis=-1)
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def _qkv(lw, z, pos, cfg):
    D, dt = cfg.head_dim, jnp.dtype(cfg.dtype)
    lead = z.shape[:-1]
    q = _dot(z, lw["wq"]).reshape(lead + (cfg.num_attention_heads, D))
    k = _dot(z, lw["wk"]).reshape(lead + (cfg.num_key_value_heads, D))
    v = _dot(z, lw["wv"]).reshape(lead + (cfg.num_key_value_heads, D))
    q = _rotary(_rms(q, lw["q_norm"], cfg.norm_eps), pos, cfg.rope_theta)
    k = _rotary(_rms(k, lw["k_norm"], cfg.norm_eps), pos, cfg.rope_theta)
    return q.astype(dt), k.astype(dt), v.astype(dt)


def _feed_forward(lw, z, valid, cfg):
    """Dense or expert feed-forward of ``z [T, H]``; ``(out, load)``
    with ``load [E]`` (``None`` for a dense layer)."""
    if "gate" not in lw:
        h = jax.nn.silu(_dot(z, lw["w1"])) * _dot(z, lw["w3"])
        return _dot(h, lw["w2"]), None
    sel, wts = moe.route(z, lw["gate"], lw.get("gate_bias"),
                         top_k=cfg.num_experts_per_tok,
                         norm_topk=cfg.norm_topk_prob,
                         scale=cfg.routed_scaling_factor)
    out = moe.expert_product(
        z.astype(jnp.dtype(cfg.dtype)), sel, wts, lw["w1"], lw["w3"],
        lw["w2"], n_experts=cfg.num_experts, held=cfg.experts_held,
        valid=valid)
    return out, moe.expert_load(sel, cfg.num_experts, valid)


# -- prefill ----------------------------------------------------------------

def _conv_prefill(lw, z, valid, pos, last, cfg):
    """``z [T, H]`` packed. A tap ``s`` slots back is the slot ``s``
    before in the stream where the token is at least ``s`` into its row,
    and zero where it is not. ``last [B]``: each row's last slot, for
    the window the decode goes on from."""
    K = cfg.conv_L_cache
    b, c, u = jnp.split(_dot(z, lw["w_in"]), 3, axis=-1)
    v = jnp.where(valid[:, None], b * u, 0.0)
    T = v.shape[0]
    y = lw["conv_w"][:, K - 1] * v
    for s in range(1, K):
        back = jnp.pad(v, ((s, 0), (0, 0)))[:T]
        y = y + lw["conv_w"][:, K - 1 - s] * jnp.where(
            (pos >= s)[:, None], back, 0.0)
    ago = jnp.arange(K - 1, -1, -1, dtype=jnp.int32)
    # ptpu: allow[materialized-gather] — [B, K, H]: the K last slots of
    # each row, the state itself
    win = jnp.take(v, jnp.maximum(last[:, None] - ago, 0), axis=0)
    win = jnp.where((pos[last][:, None] >= ago)[..., None], win, 0.0)
    return _dot(c * y, lw["w_out"]), {"win": win}


def _attention_prefill(lw, z, pos, rows, room, cfg):
    """``z [T, H]`` packed. ``q``, ``k``, ``v`` are gathered into the
    right-aligned ``[B, history]`` layout (``rows``: where each of its
    slots lies in the stream, which of them are real, and where each
    slot of the stream lies in it), attention is taken a row group at a
    time, and the output is gathered back into the stream."""
    src, real, dst = rows
    B, L = src.shape

    def to_rows(a):
        # ptpu: allow[materialized-gather] — the layout the cache keeps:
        # [B, history, heads, D] in the weights' dtype, zeros where a
        # row has no token
        flat = jnp.take(a.reshape(a.shape[0], -1), src.reshape(-1), axis=0)
        return jnp.where(real.reshape(-1, 1), flat, 0).reshape(
            (B, L) + a.shape[1:])

    q, k, v = (to_rows(a) for a in _qkv(lw, z, pos, cfg))
    g = cfg.num_attention_heads // cfg.num_key_value_heads
    n = max(r for r in range(1, min(B, ATTENTION_ROWS) + 1) if B % r == 0)

    def group(a):
        qg, kg, vg, ok = a
        return ring_attention(qg, jnp.repeat(kg, g, axis=2),
                              jnp.repeat(vg, g, axis=2), mesh=None,
                              causal=True, scale=cfg.head_dim ** -0.5,
                              key_valid=ok)

    o = jax.lax.map(group, jax.tree_util.tree_map(
        lambda a: a.reshape((B // n, n) + a.shape[1:]), (q, k, v, real)))
    # ptpu: allow[materialized-gather] — back into the stream: [T, H]
    o = jnp.take(o.reshape(B * L, -1), dst, axis=0)
    grow = ((0, 0), (0, room), (0, 0), (0, 0))
    return _dot(o, lw["wo"]), {"k": jnp.pad(k, grow), "v": jnp.pad(v, grow)}


def _head(w, x, cfg):
    z = _rms(x, w["norm_out"], cfg.norm_eps)
    return jnp.dot(z.astype(w["embed"].dtype), w["embed"].T,
                   preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("cfg", "history", "room"))
def _gen_prefill(w: dict, tokens: jax.Array, lengths: jax.Array, *,
                 cfg: DecoderConfig, history: int, room: int):
    """``tokens [T]``: the rows' tokens one behind the other, row 0
    first (any id in the spare slots behind the last row); ``lengths
    [B]``, each from 1 to ``history`` and ``T`` or under together ->
    ``(last_logits [B, V] float32, state)``, the state laid out at
    ``history`` slots and room for ``room`` more tokens."""
    T, B = tokens.shape[0], lengths.shape[0]
    lengths = lengths.astype(jnp.int32)
    ends = jnp.cumsum(lengths)
    first = ends - lengths
    slot = jnp.arange(T, dtype=jnp.int32)
    row = jnp.minimum(jnp.searchsorted(ends, slot, side="right",
                                       method="compare_all"), B - 1)
    valid, pos = slot < ends[-1], slot - first[row]
    # the right-aligned [B, history] layout: its slot ``a`` of row ``r``
    # is slot ``first[r] + a - (history - lengths[r])`` of the stream
    at = jnp.arange(history, dtype=jnp.int32)[None, :]
    lead = history - lengths
    real = at >= lead[:, None]
    rows = (jnp.where(real, (first - lead)[:, None] + at, 0), real,
            jnp.where(valid, row * history + lead[row] + pos, 0))
    # ptpu: allow[materialized-gather] — the embedding lookup itself: the
    # [T, H] it makes is the residual stream
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    states, loads = [], []
    for lw, kind in zip(w["layers"], cfg.layer_types):
        z = _rms(x, lw["op_norm"], cfg.norm_eps)
        if kind == CONV:
            o, st = _conv_prefill(lw, z, valid, pos, ends - 1, cfg)
        else:
            o, st = _attention_prefill(lw, z, pos, rows, room, cfg)
        h = x + o
        f, load = _feed_forward(lw, _rms(h, lw["ff_norm"], cfg.norm_eps),
                                valid, cfg)
        x = h + f
        states.append(st)
        if load is not None:
            loads.append(load)
    cache = jnp.arange(history + room, dtype=jnp.int32)[None, :]
    state = {"layers": states, "load": jnp.stack(loads), "pos": lengths,
             "valid": (cache >= lead[:, None]) & (cache < history),
             "filled": jnp.asarray(history, jnp.int32)}
    return _head(w, x[ends - 1], cfg), state


# -- decode -----------------------------------------------------------------

def _conv_step(lw, z, st, cfg):
    b, c, u = jnp.split(_dot(z, lw["w_in"]), 3, axis=-1)
    win = jnp.concatenate([st["win"][:, 1:], (b * u)[:, None]], axis=1)
    # elementwise, as the prefill has it: a float32 einsum would go
    # through the MXU at one bfloat16 pass
    y = sum(lw["conv_w"][:, j] * win[:, j] for j in range(win.shape[1]))
    return _dot(c * y, lw["w_out"]), {"win": win}


def _attention_step(lw, z, st, valid, pos, at, cfg):
    """One query a row against its cache; the new key and value land in
    slot ``at``, which ``valid`` already counts."""
    q, k, v = _qkv(lw, z, pos, cfg)
    ks = jax.lax.dynamic_update_slice_in_dim(st["k"], k[:, None], at, 1)
    vs = jax.lax.dynamic_update_slice_in_dim(st["v"], v[:, None], at, 1)
    B, nkv, D = k.shape
    s = jnp.einsum("bgrd,bsgd->bgrs", q.reshape(B, nkv, -1, D), ks,
                   preferred_element_type=jnp.float32) * D ** -0.5
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    o = jnp.einsum("bgrs,bsgd->bgrd",
                   jax.nn.softmax(s, axis=-1).astype(vs.dtype), vs,
                   preferred_element_type=jnp.float32)
    return _dot(o.reshape(B, -1), lw["wo"]), {"k": ks, "v": vs}


def _layer_step(lw, kind, x, st, valid, pos, at, cfg):
    z = _rms(x, lw["op_norm"], cfg.norm_eps)
    if kind == CONV:
        o, st = _conv_step(lw, z, st, cfg)
    else:
        o, st = _attention_step(lw, z, st, valid, pos, at, cfg)
    h = x + o
    f, load = _feed_forward(lw, _rms(h, lw["ff_norm"], cfg.norm_eps),
                            None, cfg)
    return h + f, st, load


def _decode_step(w, state, tok, cfg):
    """Append ``tok [B]`` to every row: ``(logits [B, V], state, load
    [expert layers, E])``."""
    at, pos = state["filled"], state["pos"]
    valid = jax.lax.dynamic_update_slice_in_dim(
        state["valid"], jnp.ones((tok.shape[0], 1), bool), at, 1)
    x = jnp.take(w["embed"], tok, axis=0).astype(jnp.float32)
    states, loads = [], []
    for lw, kind, st in zip(w["layers"], cfg.layer_types, state["layers"]):
        x, st, load = _layer_step(lw, kind, x, st, valid, pos, at, cfg)
        states.append(st)
        if load is not None:
            loads.append(load)
    new = {**state, "layers": states, "valid": valid, "filled": at + 1,
           "pos": pos + 1}
    return _head(w, x, cfg), new, jnp.stack(loads)


@functools.partial(jax.jit, static_argnames=("cfg", "steps"),
                   donate_argnames=("state",))
def _gen_decode(w: dict, state: dict, first: jax.Array, *,
                cfg: DecoderConfig, steps: int):
    """Greedy generation of ``steps`` tokens a row from the prefill's
    ``state`` (donated) and its last logits ``first [B, V]``: token 0 is
    their argmax, then ``steps - 1`` forward passes. Returns ``(tokens
    [B, steps] int32, scores [B, steps] float32, expert_load, state)``:
    a step's score is the chosen token's logit; ``expert_load`` is
    ``(prefill [expert layers, E], decode [steps - 1, expert layers,
    E])`` tokens per expert; the state comes back so that the donated
    buffers are the ones the loop writes (and a caller with room left
    can go on from it)."""
    def pick(logits):
        return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                jnp.max(logits, axis=-1))

    def step(carry, _):
        state, tok = carry
        logits, state, load = _decode_step(w, state, tok, cfg)
        tok, score = pick(logits)
        return (state, tok), (tok, score, load)

    tok0, score0 = pick(first)
    (state, _), (toks, scores, loads) = jax.lax.scan(
        step, (state, tok0), None, length=steps - 1)
    tokens = jnp.concatenate([tok0[None], toks], axis=0).T
    scores = jnp.concatenate([score0[None], scores], axis=0).T
    return tokens, scores, (state["load"], loads), state
