"""The benchmark's copy of the plain reference of the decoder block stack
(``predictionio_tpu/models/decoder_reference.py``; one test holds the
two to identical outputs), as the ``xing4_0`` family's cell uses it.
Nothing here imports the program.

The full forward pass in straightforward ``jax.numpy``, float32, at
``highest`` matmul precision, one sequence at a time: no cache, no
batching, no padding, no kernels, the experts one after the other,
every head's keys and values laid out from the latents (the EXPANDED
form, where the served decode attends over the latents themselves).
``cfg`` is the configuration file's dict with the family's published
key names; ``weights`` is ``{"embed", "norm_out", "head", "layers":
[layer dict, ...]}`` and is widened to float32 where it is used, so the
served bfloat16 weights give the float32 result OF THOSE WEIGHTS.

The equations (``n`` = ``hc_mult`` streams ``X [T, n, H]``; RMSNorm
``norm`` with ``rms_norm_eps``):

- a sub-block ``F`` (attention with ``op_norm``, feed-forward with
  ``ff_norm``) on the residual path: ``x~ = vec(X) / sqrt(mean(vec(X)^2)
  + hc_eps)``; ``H_pre = sigmoid(a_pre x~ phi_pre + b_pre)``; ``H_post =
  2 sigmoid(a_post x~ phi_post + b_post)``; ``M = exp(clip(a_res mat(x~
  phi_res) + b_res, -30, 30))``, then ``hc_sinkhorn_iters`` times rows
  over (their sums + ``hc_eps``), columns likewise: ``H_res``; ``u =
  H_pre X``; ``y = F(norm(u))``; ``X' = H_res X + H_post^T y``. The
  embedding enters ``n`` times; the streams are summed before
  ``norm_out`` and the head.
- latent attention: ``c_q = norm_768(z W_qa)``; ``[q_nope | q_rope] =
  c_q W_qb`` a head; ``[c | r] = z W_kva``; ``c_kv = norm_512(c)``;
  ``k_rope = rope(r)`` ONE for all heads; ``[k_nope | v] = c_kv W_kvb``
  a head; scores at ``(nope + rope)^-0.5 m^2`` with ``m = 0.1
  mscale_all_dim ln(factor) + 1``; causal softmax; ``W_o``. Rotary:
  rotate-half over the ``rope`` dimensions, yarn's blended frequencies,
  ``yarn(mscale) / yarn(mscale_all_dim)`` on cos and sin.
- layers under ``first_k_dense_replace``: SwiGLU ``intermediate_size``
  wide; the rest: sigmoid scores, the top ``k`` of score + bias
  selected, weighted by the scores over their sum + 1e-6, times
  ``routed_scaling_factor``, plus the shared SwiGLU at weight 1.

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
    return float(cfg["rms_norm_eps"])


def rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f(gain)


def yarn_mscale(scaling, key):
    """``0.1 scaling[key] ln(factor) + 1`` (yarn, a factor over 1)."""
    factor = float(scaling.get("factor", 1.0))
    if scaling.get("type") != "yarn" or factor <= 1:
        return 1.0
    # ptpu: allow[unguarded-domain] — factor is over 1 here
    return 0.1 * float(scaling.get(key, 1.0)) * math.log(factor) + 1.0


def inverse_frequencies(rotated, theta, scaling):
    """``inv [rotated / 2]``: plain ``1 / theta^(2i/d)``, or yarn's blend
    with the interpolated ``1 / (factor theta^(2i/d))`` over the
    dimensions that turn ``beta_fast`` .. ``beta_slow`` times over the
    original context (a linear ramp between)."""
    # ptpu: allow[unguarded-domain] — rotated is a static size, never 0
    plain = theta ** (-jnp.arange(0, rotated, 2, dtype=F32) / rotated)
    if scaling.get("type", "default") == "default":
        return plain
    factor = float(scaling["factor"])
    ctx = float(scaling["original_max_position_embeddings"])

    # ptpu: allow[unguarded-domain] — a config's positive constants
    per_turn = rotated / (2 * math.log(theta))

    def turns(n):  # the dimension that turns n times over ctx positions
        # ptpu: allow[unguarded-domain] — a config's positive constants
        return per_turn * math.log(ctx / (n * 2 * math.pi))

    low = max(math.floor(turns(float(scaling.get("beta_fast", 32)))), 0)
    high = min(math.ceil(turns(float(scaling.get("beta_slow", 1)))),
               rotated - 1)
    high = high + 0.001 if low == high else high
    w = jnp.clip((jnp.arange(rotated // 2, dtype=F32) - low)
                 / (high - low), 0.0, 1.0)
    return plain / factor * w + plain * (1.0 - w)


def rotary(x, cfg):
    """``x [T, heads, rope]`` at positions ``0 .. T-1``, rotate-half."""
    T, _, R = x.shape
    scaling = dict(cfg.get("rope_scaling") or {})
    inv = inverse_frequencies(R, float(cfg["rope_theta"]), scaling)
    factor = yarn_mscale(scaling, "mscale") \
        / yarn_mscale(scaling, "mscale_all_dim")
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    half = jnp.concatenate([-x[..., R // 2:], x[..., :R // 2]], axis=-1)
    return x * (jnp.cos(ang) * factor) + half * (jnp.sin(ang) * factor)


def latent_attention_op(lw, z, cfg, *, yarn_scale=True, query_block=None):
    """Latent attention over one sequence ``z [T, H]``, EXPANDED.
    ``query_block``: queries that many at a time (the same numbers;
    scores ``[heads, block, T]`` and never ``[heads, T, T]``)."""
    T = z.shape[0]
    nq = int(cfg["num_attention_heads"])
    dn, dr, dv = (int(cfg[k]) for k in ("qk_nope_head_dim",
                                        "qk_rope_head_dim", "v_head_dim"))
    rkv = int(cfg["kv_lora_rank"])
    cq = rms(z @ _f(lw["w_qa"]), lw["q_a_norm"], _eps(cfg))
    q = (cq @ _f(lw["w_qb"])).reshape(T, nq, dn + dr)
    kv = z @ _f(lw["w_kva"])
    c_kv = rms(kv[:, :rkv], lw["kv_a_norm"], _eps(cfg))
    k_rope = rotary(kv[:, None, rkv:], cfg)          # [T, 1, rope]
    q = jnp.concatenate([q[..., :dn], rotary(q[..., dn:], cfg)], axis=-1)
    up = (c_kv @ _f(lw["w_kvb"])).reshape(T, nq, dn + dv)
    k = jnp.concatenate([up[..., :dn],
                         jnp.broadcast_to(k_rope, (T, nq, dr))], axis=-1)
    v = up[..., dn:]
    m = yarn_mscale(dict(cfg.get("rope_scaling") or {}),
                    "mscale_all_dim") if yarn_scale else 1.0
    scale = (dn + dr) ** -0.5 * m * m
    at = jnp.arange(T)

    def attend(qs, i):  # queries ``qs [n, heads, D]`` at positions ``i``
        s = jnp.einsum("qhd,khd->hqk", qs, k) * scale
        p = jax.nn.softmax(
            jnp.where((at[None, :] <= i[:, None])[None], s, -jnp.inf),
            axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    if query_block is None:
        o = attend(q, at)
    else:
        bq = int(query_block)
        pad = -T % bq
        o = jax.lax.map(
            lambda a: attend(*a),
            (jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
                -1, bq, nq, dn + dr),
             jnp.arange(T + pad).reshape(-1, bq))
        ).reshape(T + pad, nq, dv)[:T]
    return o.reshape(T, nq * dv) @ _f(lw["wo"])


def hyper_coefficients(lw, sub, X, cfg, *, iters=None):
    """``(H_pre [T, n], H_post [T, n], H_res [T, n, n])`` of sub-block
    ``sub`` (``op`` or ``ff``) from the streams ``X [T, n, H]``;
    ``iters``: Sinkhorn passes (``hc_sinkhorn_iters``)."""
    T, n, H = X.shape
    eps = float(cfg["hc_eps"])
    flat = X.reshape(T, n * H)
    xt = flat / jnp.sqrt(jnp.mean(flat * flat, axis=-1, keepdims=True) + eps)
    a = _f(lw[f"hc_{sub}_a"])
    pre = jax.nn.sigmoid(a[0] * (xt @ _f(lw[f"hc_{sub}_phi_pre"]))
                         + _f(lw[f"hc_{sub}_b_pre"]))
    post = 2.0 * jax.nn.sigmoid(a[1] * (xt @ _f(lw[f"hc_{sub}_phi_post"]))
                                + _f(lw[f"hc_{sub}_b_post"]))
    m = jnp.exp(jnp.clip(
        a[2] * (xt @ _f(lw[f"hc_{sub}_phi_res"])).reshape(T, n, n)
        + _f(lw[f"hc_{sub}_b_res"]),
        float(cfg["mhc_h_res_clamp_min"]), float(cfg["mhc_h_res_clamp_max"])))
    for _ in range(int(cfg["hc_sinkhorn_iters"] if iters is None else iters)):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)   # rows
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)   # columns
    return pre, post, m


def hyper_connection(lw, sub, X, fn, cfg, *, iters=None):
    """``X' = H_res X + H_post^T fn(H_pre X)`` over ``X [T, n, H]``."""
    pre, post, res = hyper_coefficients(lw, sub, X, cfg, iters=iters)
    y = fn(jnp.einsum("tn,tnh->th", pre, X))
    return jnp.einsum("tij,tjh->tih", res, X) \
        + post[:, :, None] * y[:, None, :]


def _around(lw, sub, x, fn, cfg, iters=None):
    """A sub-block on the residual path: a plain sum, or the ``n``
    streams' read, write and mix."""
    if int(cfg.get("hc_mult") or 1) == 1:
        return x + fn(x)
    return hyper_connection(lw, sub, x, fn, cfg, iters=iters)


def dense_ff(lw, z, names=("w1", "w3", "w2")):
    w1, w3, w2 = (_f(lw[n]) for n in names)
    return (jax.nn.silu(z @ w1) * (z @ w3)) @ w2


def route(lw, z, cfg):
    """The dense ``[T, E]`` matrix of routing weights (zero where an
    expert is not selected)."""
    E, k = int(cfg["n_routed_experts"]), int(cfg["num_experts_per_tok"])
    s = jax.nn.sigmoid(z @ _f(lw["gate"]))
    _, sel = jax.lax.top_k(s + _f(lw["gate_bias"]), k)
    w = jnp.take_along_axis(s, sel, axis=-1)
    if cfg.get("norm_topk_prob"):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    w = w * float(cfg.get("routed_scaling_factor", 1.0))
    return jnp.sum(jax.nn.one_hot(sel, E, dtype=F32) * w[..., None],
                   axis=1)


def expert_ff(lw, z, cfg):
    """The routed experts, one after the other (``lax.scan`` over the
    expert axis); the shared one is added by :func:`feed_forward`."""
    weights = route(lw, z, cfg)

    def one(out, expert):
        w1, w3, w2, w = expert
        y = (jax.nn.silu(z @ _f(w1)) * (z @ _f(w3))) @ _f(w2)
        return out + w[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(z),
                          (lw["w1"], lw["w3"], lw["w2"], weights.T))
    return out


def operator(lw, l, x, cfg, *, sinkhorn_iters=None, **how):
    """The attention sub-block of layer ``l`` over one sequence ``x [T,
    n, H]``; ``how`` goes to :func:`latent_attention_op`."""
    def op(u):
        return latent_attention_op(
            lw, rms(u, lw["op_norm"], _eps(cfg)), cfg, **how)

    with jax.default_matmul_precision("highest"):
        return _around(lw, "op", x, op, cfg, sinkhorn_iters)


def feed_forward(lw, l, h, cfg, *, sinkhorn_iters=None):
    """The feed-forward sub-block of layer ``l`` over tokens ``h [T, n,
    H]``; every token on its own."""
    def ff(u):
        z = rms(u, lw["ff_norm"], _eps(cfg))
        if l < int(cfg["first_k_dense_replace"]):
            return dense_ff(lw, z)
        out = expert_ff(lw, z, cfg)
        if int(cfg.get("n_shared_experts") or 0):
            out = out + dense_ff(lw, z, ("s1", "s3", "s2"))
        return out

    with jax.default_matmul_precision("highest"):
        return _around(lw, "ff", h, ff, cfg, sinkhorn_iters)


def layer(lw, l, x, cfg):
    """Layer ``l`` over one sequence ``x [T, n, H]``."""
    return feed_forward(lw, l, operator(lw, l, x, cfg), cfg)


def embed(weights, tokens):
    return _f(weights["embed"])[jnp.asarray(tokens)]


def streams_in(x, cfg):
    """``x [T, H]`` as the residual path takes it: ``hc_mult`` copies."""
    n = int(cfg.get("hc_mult") or 1)
    return x if n == 1 else jnp.repeat(x[:, None, :], n, axis=1)


def streams_out(x, cfg):
    """What the head reads: the ``hc_mult`` streams summed."""
    return x if int(cfg.get("hc_mult") or 1) == 1 else jnp.sum(x, axis=-2)


def head(weights, x, cfg):
    with jax.default_matmul_precision("highest"):
        return rms(x, weights["norm_out"], _eps(cfg)) @ _f(weights["head"]).T


def forward(weights, tokens, cfg):
    """Logits ``[T, V]`` of one sequence of token ids."""
    x = streams_in(embed(weights, tokens), cfg)
    for l, lw in enumerate(weights["layers"]):
        x = layer(lw, l, x, cfg)
    return head(weights, streams_out(x, cfg), cfg)


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
