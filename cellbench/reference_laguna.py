"""The benchmark's copy of the plain reference of the decoder block stack
(``predictionio_tpu/models/decoder_reference.py``; one test holds the
two to identical outputs), as the ``laguna`` family's cell uses it.
Nothing here imports the program.

The full forward pass in straightforward ``jax.numpy``, float32, at
``highest`` matmul precision, one sequence at a time: no cache, no
batching, no padding, no kernels, the experts one after the other.
``cfg`` is the configuration file's dict; ``weights`` is ``{"embed",
"norm_out", "head", "layers": [layer dict, ...]}`` and is widened to
float32 where it is used, so the served bfloat16 weights give the
float32 result OF THOSE WEIGHTS. The equations (full and sliding
attention with their own head counts and rotary, the head gate, the
routed and the shared experts, the untied head) are written out in the
program's copy and in PERF.md; the three conventions the published
config leaves open are the named arguments ``head_gate``, ``qk_norm``
and ``scores``. ``attention_op(..., query_block=512)`` takes the queries
a block at a time, so a 4,127-token sequence's scores are ``[8, 512,
4127]`` a key-value head and never ``[heads, T, T]``;
``window=None`` is the ``no_window`` control.

``served_gaps`` is what ``correct`` reads: a served answer against the
reference's logits over its history plus the tokens served.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f(a):
    return jnp.asarray(a).astype(F32)


def _eps(cfg):
    return float(cfg["rms_norm_eps"] if "rms_norm_eps" in cfg
                 else cfg["norm_eps"])


def _heads(cfg, l):
    per_layer = cfg.get("num_attention_heads_per_layer")
    return int(per_layer[l] if per_layer else cfg["num_attention_heads"])


def _is_dense(cfg, l):
    kinds = cfg.get("mlp_layer_types")
    return kinds[l] == "dense" if kinds \
        else l < int(cfg["num_dense_layers"])


def rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f(gain)


def inverse_frequencies(rotated, rope):
    """``(inv [rotated / 2], factor)`` of one layer kind's rotary
    (``rope``: ``rope_theta`` and, for ``yarn``, its keys)."""
    theta = float(rope["rope_theta"])
    # ptpu: allow[unguarded-domain] — rotated is a static size, never 0
    plain = theta ** (-jnp.arange(0, rotated, 2, dtype=F32) / rotated)
    if rope.get("rope_type", "default") == "default":
        return plain, 1.0
    factor = float(rope["factor"])
    ctx = float(rope["original_max_position_embeddings"])

    # ptpu: allow[unguarded-domain] — a config's positive constants
    per_turn = rotated / (2 * math.log(theta))

    def turns(n):  # the dimension that turns n times over ctx positions
        # ptpu: allow[unguarded-domain] — a config's positive constants
        return per_turn * math.log(ctx / (n * 2 * math.pi))

    low = max(math.floor(turns(float(rope.get("beta_fast", 32)))), 0)
    high = min(math.ceil(turns(float(rope.get("beta_slow", 1)))),
               rotated - 1)
    high = high + 0.001 if low == high else high
    w = jnp.clip((jnp.arange(rotated // 2, dtype=F32) - low)
                 / (high - low), 0.0, 1.0)
    attention = rope.get("attention_factor")
    return plain / factor * w + plain * (1.0 - w), float(
        0.1 * math.log(factor) + 1.0 if attention is None else attention)


def rotary(x, rope):
    """``x [T, heads, D]`` at positions ``0 .. T-1``; ``rope`` a layer
    kind's parameters, or a bare ``rope_theta`` over the whole head."""
    T, _, D = x.shape
    if not isinstance(rope, dict):
        rope = {"rope_theta": rope}
    R = int(D * float(rope.get("partial_rotary_factor", 1.0)))
    inv, factor = inverse_frequencies(R, rope)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    xr = x[..., :R]
    half = jnp.concatenate([-xr[..., R // 2:], xr[..., :R // 2]], axis=-1)
    out = xr * (jnp.cos(ang) * factor) + half * (jnp.sin(ang) * factor)
    return out if R == D else jnp.concatenate([out, x[..., R:]], axis=-1)


def conv_op(lw, z, cfg):
    K = int(cfg["conv_L_cache"])
    b, c, u = jnp.split(z @ _f(lw["w_in"]), 3, axis=-1)
    v = b * u
    T = v.shape[0]
    vp = jnp.concatenate([jnp.zeros((K - 1, v.shape[1]), F32), v])
    w = _f(lw["conv_w"])
    y = sum(w[:, j] * vp[j:j + T] for j in range(K))
    return (c * y) @ _f(lw["w_out"])


def attention_op(lw, z, cfg, l=0, *, head_gate="scalar", qk_norm=True,
                 window="published", query_block=None):
    """Layer ``l``'s attention over one sequence ``z [T, H]``.
    ``window="published"``: ``sliding_window`` in a sliding layer, none
    in a full one (``None``: every layer sees every earlier key, the
    benchmark's ``no_window`` control). ``query_block``: queries taken
    that many at a time (the same numbers; scores of ``[heads of one
    key-value head, block, T]`` and never ``[heads, T, T]``)."""
    T = z.shape[0]
    kind = cfg["layer_types"][l]
    nq, nkv = _heads(cfg, l), int(cfg["num_key_value_heads"])
    D = int(cfg.get("head_dim") or cfg["hidden_size"] // nq)
    rope = (cfg.get("rope_parameters") or {}).get(
        kind, {"rope_theta": cfg.get("rope_theta")})
    if window == "published":
        window = cfg.get("sliding_window") \
            if kind == "sliding_attention" else None
    q = (z @ _f(lw["wq"])).reshape(T, nq, D)
    k = (z @ _f(lw["wk"])).reshape(T, nkv, D)
    v = (z @ _f(lw["wv"])).reshape(T, nkv, D)
    if qk_norm:
        q = rms(q, lw["q_norm"], _eps(cfg))
        k = rms(k, lw["k_norm"], _eps(cfg))
    q, k = rotary(q, rope), rotary(k, rope)
    at = jnp.arange(T)

    def seen(i):  # which keys queries at positions ``i`` see
        see = at[None, :] <= i[:, None]
        return see if window is None \
            else see & (at[None, :] > i[:, None] - int(window))

    if query_block is None:
        kk = jnp.repeat(k, nq // nkv, axis=1)
        vv = jnp.repeat(v, nq // nkv, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, kk) * D ** -0.5
        p = jax.nn.softmax(jnp.where(seen(at)[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, vv)
    else:
        bq = int(query_block)
        pad = -T % bq
        qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
            -1, bq, nkv, nq // nkv, D)

        def block(a):
            qs, i = a  # [bq, kv heads, heads of one, D], [bq]
            s = jnp.einsum("qgrd,kgd->grqk", qs, k) * D ** -0.5
            p = jax.nn.softmax(jnp.where(seen(i), s, -jnp.inf), axis=-1)
            return jnp.einsum("grqk,kgd->qgrd", p, v)

        o = jax.lax.map(block, (qb, jnp.arange(T + pad).reshape(-1, bq))
                        ).reshape(T + pad, nq, D)[:T]
    if cfg.get("gating"):
        gate = jax.nn.sigmoid(z @ _f(lw["wg"]))
        o = o * gate[..., None] if head_gate == "scalar" \
            else o * gate.reshape(T, nq, D)
    return o.reshape(T, nq * D) @ _f(lw["wo"])


def dense_ff(lw, z, names=("w1", "w3", "w2")):
    w1, w3, w2 = (_f(lw[n]) for n in names)
    return (jax.nn.silu(z @ w1) * (z @ w3)) @ w2


def route(lw, z, cfg, *, scores="sigmoid"):
    """The dense ``[T, E]`` matrix of routing weights (zero where an
    expert is not selected)."""
    E, k = int(cfg["num_experts"]), int(cfg["num_experts_per_tok"])
    logits = z @ _f(lw["gate"])
    s = jax.nn.sigmoid(logits) if scores == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    pick = s + _f(lw["gate_bias"]) if cfg.get("use_expert_bias") else s
    _, sel = jax.lax.top_k(pick, k)
    w = jnp.take_along_axis(s, sel, axis=-1)
    if cfg.get("norm_topk_prob"):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    w = w * float(cfg.get("moe_routed_scaling_factor",
                          cfg.get("routed_scaling_factor", 1.0)))
    return jnp.sum(jax.nn.one_hot(sel, E, dtype=F32) * w[..., None],
                   axis=1)


def expert_ff(lw, z, cfg, *, scores="sigmoid"):
    """The experts held here, one after the other (a loop the compiler
    sees once: ``lax.scan`` over the expert axis): ``lw['w1'][i]`` is
    expert ``held[i]``'s. The ROUTED experts only: the shared one is
    added by :func:`feed_forward`."""
    held = cfg.get("experts_held") or range(int(cfg["num_experts"]))
    weights = route(lw, z, cfg, scores=scores)[:, jnp.asarray(list(held))]

    def one(out, expert):
        w1, w3, w2, w = expert
        y = (jax.nn.silu(z @ _f(w1)) * (z @ _f(w3))) @ _f(w2)
        return out + w[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(z),
                          (lw["w1"], lw["w3"], lw["w2"], weights.T))
    return out


def operator(lw, l, x, cfg, **how):
    """``h = x + op_l(n_op(x))`` over one sequence ``x [T, H]``; ``how``
    goes to :func:`attention_op`."""
    with jax.default_matmul_precision("highest"):
        z = rms(x, lw["op_norm"], _eps(cfg))
        if cfg["layer_types"][l] == "conv":
            return x + conv_op(lw, z, cfg)
        return x + attention_op(lw, z, cfg, l, **how)


def feed_forward(lw, l, h, cfg, *, scores="sigmoid"):
    """``y = h + ff_l(n_ff(h))`` over tokens ``h [T, H]``; every token
    on its own."""
    with jax.default_matmul_precision("highest"):
        z = rms(h, lw["ff_norm"], _eps(cfg))
        if _is_dense(cfg, l):
            return h + dense_ff(lw, z)
        ff = expert_ff(lw, z, cfg, scores=scores)
        if cfg.get("shared_expert_intermediate_size"):
            ff = ff + dense_ff(lw, z, ("s1", "s3", "s2"))
        return h + ff


def layer(lw, l, x, cfg):
    """Layer ``l`` over one sequence ``x [T, H]``."""
    return feed_forward(lw, l, operator(lw, l, x, cfg), cfg)


def embed(weights, tokens):
    return _f(weights["embed"])[jnp.asarray(tokens)]


def head(weights, x, cfg):
    table = weights["embed"] if cfg.get("tie_word_embeddings", True) \
        else weights["head"]
    with jax.default_matmul_precision("highest"):
        return rms(x, weights["norm_out"], _eps(cfg)) @ _f(table).T


def forward(weights, tokens, cfg):
    """Logits ``[T, V]`` of one sequence of token ids."""
    x = embed(weights, tokens)
    for l, lw in enumerate(weights["layers"]):
        x = layer(lw, l, x, cfg)
    return head(weights, x, cfg)


def int8_round_trip(a):
    """Symmetric int8 with one scale per output column and back: the
    control one precision below the configuration's."""
    a = _f(a)
    scale = jnp.max(jnp.abs(a), axis=-2, keepdims=True) / 127.0
    return jnp.round(a / scale) * scale


def served_gaps(logits, tokens, scores):
    """One answer against the reference. ``logits [n, V]`` are the
    reference's at the ``n`` generated positions (teacher-forced on the
    served tokens), ``tokens`` / ``scores [n]`` what was served. Per
    position, in units of the spread (standard deviation over the
    vocabulary) of that position's reference logits: ``score`` = |served
    score - reference logit of the served token| and ``rank`` =
    reference's largest logit - reference logit of the served token
    (greedy has to pick within rounding of the best)."""
    at = jnp.take_along_axis(logits, jnp.asarray(tokens)[:, None],
                             axis=1)[:, 0]
    unit = jnp.std(logits, axis=1)
    return (jnp.abs(jnp.asarray(scores, F32) - at) / unit,
            (jnp.max(logits, axis=1) - at) / unit)
