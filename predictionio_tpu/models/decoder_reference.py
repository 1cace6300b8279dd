"""The plain reference of ``models/decoder.py``: the full forward pass of
the ``lfm2_moe`` block stack in straightforward ``jax.numpy``, float32,
at ``highest`` matmul precision, one sequence at a time. No cache, no
batching, no padding, no kernels; the experts one after the other.

``cfg`` is the published ``config.json`` as a dict (plus ``head_dim``
where the source gives none, and ``experts_held`` for a chip's share).
``weights`` is ``{"embed": [V, H], "norm_out": [H], "layers": [layer
dict, ...]}``, the tree ``decoder.init_weights`` makes; they are
widened to float32 where they are used, so bfloat16 weights give
the float32 result OF THOSE WEIGHTS.

The equations (``H`` hidden size, ``n`` RMSNorm with ``norm_eps``):

- layer ``l``: ``h = x + op_l(n_op(x))``, ``y = h + ff_l(n_ff(h))``.
- ``conv``: ``[B, C, u] = split3(z W_in)``; ``v = B * u``; ``c_t =
  sum_j w[:, j] v_{t-K+1+j}`` per channel (depthwise, causal, ``K =
  conv_L_cache``, zeros before the sequence); ``out = (C * c) W_out``.
- ``full_attention``: ``q``, ``k``, ``v`` by head; RMSNorm over each
  head of ``q`` and of ``k`` (own gains); rotary over the whole head
  (rotate-half, ``rope_theta``); each key-value head serves
  ``heads / kv_heads`` consecutive query heads; causal softmax at scale
  ``head_dim ** -0.5``; ``out = attn W_o``.
- dense feed-forward: ``(silu(z W_1) * z W_3) W_2``.
- expert block: ``s = sigmoid(z W_g)``; the top ``k`` of ``s + b`` are
  selected; their weights are ``s`` WITHOUT ``b``; ``norm_topk_prob``:
  ``w / (sum w + 1e-6)``; times ``routed_scaling_factor``; ``out =
  sum_e w_e (silu(z W_1e) * z W_3e) W_2e``. No capacity, no drop.
- ``logits = n_out(x) E^T`` with the embedding ``E`` (tied).

Departures from the published implementation, all listed in the
benchmark configuration's ``assumed``: ``head_dim = hidden / heads``
(the source gives null), the tied head, a conv kernel exactly
``conv_L_cache`` wide, and seeded weights in place of trained ones.
``cellbench/reference_lfm2.py`` is the benchmark's copy of this file;
``tests/test_decoder.py`` holds the two to identical outputs.
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
