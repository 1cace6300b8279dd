"""The benchmark's copy of the plain reference of the ``lfm2_moe`` block
stack (``predictionio_tpu/models/decoder_reference.py``; one test holds
the two to identical outputs). Nothing here imports the program.

The full forward pass in straightforward ``jax.numpy``, float32, at
``highest`` matmul precision, one sequence at a time: no cache, no
batching, no padding, no kernels, the experts one after the other. ``cfg`` is
the configuration file's dict; ``weights`` is ``{"embed", "norm_out",
"layers": [layer dict, ...]}`` and is widened to float32 where it is
used, so the served bfloat16 weights give the float32 result OF THOSE
WEIGHTS. The equations are written out in the program's copy and in
PERF.md.

``served_gaps`` is what ``correct`` reads: a served answer against the
reference's logits over its history plus the tokens served.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f(a):
    return jnp.asarray(a).astype(F32)


def rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f(gain)


def rotary(x, theta):
    """``x [T, heads, D]`` at positions ``0 .. T-1``."""
    T, _, D = x.shape
    # ptpu: allow[unguarded-domain] — D is the static head size, never 0
    inv = theta ** (-jnp.arange(0, D, 2, dtype=F32) / D)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    half = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return x * jnp.cos(ang) + half * jnp.sin(ang)


def conv_op(lw, z, cfg):
    K = int(cfg["conv_L_cache"])
    b, c, u = jnp.split(z @ _f(lw["w_in"]), 3, axis=-1)
    v = b * u
    T = v.shape[0]
    vp = jnp.concatenate([jnp.zeros((K - 1, v.shape[1]), F32), v])
    w = _f(lw["conv_w"])
    y = sum(w[:, j] * vp[j:j + T] for j in range(K))
    return (c * y) @ _f(lw["w_out"])


def attention_op(lw, z, cfg):
    T = z.shape[0]
    nq, nkv = int(cfg["num_attention_heads"]), \
        int(cfg["num_key_value_heads"])
    D = int(cfg.get("head_dim") or cfg["hidden_size"] // nq)
    eps, theta = float(cfg["norm_eps"]), float(cfg["rope_theta"])
    q = (z @ _f(lw["wq"])).reshape(T, nq, D)
    k = (z @ _f(lw["wk"])).reshape(T, nkv, D)
    v = (z @ _f(lw["wv"])).reshape(T, nkv, D)
    q = rotary(rms(q, lw["q_norm"], eps), theta)
    k = rotary(rms(k, lw["k_norm"], eps), theta)
    k = jnp.repeat(k, nq // nkv, axis=1)
    v = jnp.repeat(v, nq // nkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * D ** -0.5
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v).reshape(T, nq * D) \
        @ _f(lw["wo"])


def dense_ff(lw, z):
    return (jax.nn.silu(z @ _f(lw["w1"])) * (z @ _f(lw["w3"]))) \
        @ _f(lw["w2"])


def route(lw, z, cfg):
    """The dense ``[T, E]`` matrix of routing weights (zero where an
    expert is not selected)."""
    E, k = int(cfg["num_experts"]), int(cfg["num_experts_per_tok"])
    s = jax.nn.sigmoid(z @ _f(lw["gate"]))
    pick = s + _f(lw["gate_bias"]) if cfg.get("use_expert_bias") else s
    _, sel = jax.lax.top_k(pick, k)
    w = jnp.take_along_axis(s, sel, axis=-1)
    if cfg.get("norm_topk_prob"):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    w = w * float(cfg.get("routed_scaling_factor", 1.0))
    return jnp.sum(jax.nn.one_hot(sel, E, dtype=F32) * w[..., None],
                   axis=1)


def expert_ff(lw, z, cfg):
    """The experts held here, one after the other (a loop the compiler
    sees once: ``lax.scan`` over the expert axis): ``lw['w1'][i]`` is
    expert ``held[i]``'s."""
    held = cfg.get("experts_held") or range(int(cfg["num_experts"]))
    weights = route(lw, z, cfg)[:, jnp.asarray(list(held))]

    def one(out, expert):
        w1, w3, w2, w = expert
        y = (jax.nn.silu(z @ _f(w1)) * (z @ _f(w3))) @ _f(w2)
        return out + w[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(z),
                          (lw["w1"], lw["w3"], lw["w2"], weights.T))
    return out


def operator(lw, l, x, cfg):
    """``h = x + op_l(n_op(x))`` over one sequence ``x [T, H]``."""
    with jax.default_matmul_precision("highest"):
        z = rms(x, lw["op_norm"], float(cfg["norm_eps"]))
        op = conv_op if cfg["layer_types"][l] == "conv" else attention_op
        return x + op(lw, z, cfg)


def feed_forward(lw, l, h, cfg):
    """``y = h + ff_l(n_ff(h))`` over tokens ``h [T, H]``; every token
    on its own."""
    with jax.default_matmul_precision("highest"):
        z = rms(h, lw["ff_norm"], float(cfg["norm_eps"]))
        ff = dense_ff(lw, z) if l < int(cfg["num_dense_layers"]) \
            else expert_ff(lw, z, cfg)
        return h + ff


def layer(lw, l, x, cfg):
    """Layer ``l`` over one sequence ``x [T, H]``."""
    return feed_forward(lw, l, operator(lw, l, x, cfg), cfg)


def embed(weights, tokens):
    return _f(weights["embed"])[jnp.asarray(tokens)]


def head(weights, x, cfg):
    with jax.default_matmul_precision("highest"):
        return rms(x, weights["norm_out"], float(cfg["norm_eps"])) \
            @ _f(weights["embed"]).T


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
