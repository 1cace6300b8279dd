"""The benchmark's copy of the plain reference of the ``nemotron_h`` block
stack (``predictionio_tpu/models/decoder_reference.py``; one test holds
the two to the same outputs). Nothing here imports the program, and
nothing is shared with its kernels: the state-space recurrence runs
TOKEN BY TOKEN (``lax.scan``), where the served prefill runs it in
chunks of 128 and the served decode one token a dispatched step; every
expert of the chip's share is applied DENSELY to every token (no sort,
no grouped product, no kernel), weighted by the router's dense matrix.

The full forward pass in straightforward ``jax.numpy``, float32, at
``highest`` matmul precision, one sequence at a time: no cache, no
batching, no padding, full causal attention. ``cfg`` is the
configuration file's dict with the family's published key names (plus
``router_experts`` and ``experts_held``: the chip's share); ``weights``
is ``{"embed", "head", "norm_out", "layers": [layer dict, ...]}`` and is
widened to float32 where it is used, so the served bfloat16 weights give
the float32 result OF THOSE WEIGHTS.

The equations (``n`` RMSNorm with its own gain, ``layer_norm_epsilon``).
``x_0 = E[tok]``; layer ``l`` is ONE sub-block by the letter
``hybrid_override_pattern[l]``: ``x_{l+1} = x_l + F_l(n_l(x_l))``; logits
``n(x_L) W_head^T`` (untied, over the slice of the vocabulary held).

- ``*``: ``q, k, v = z W_q, z W_k, z W_v`` (``num_attention_heads`` over
  ``num_key_value_heads`` heads of ``head_dim``), no rotary, no per-head
  norm, no bias, causal softmax of ``q k^T / sqrt(head_dim)``, ``W_o``.
- ``M`` (Mamba-2; ``I = mamba_num_heads x mamba_head_dim``, ``G =
  n_groups``, ``N = ssm_state_size``): ``[z | xBC | dt] = u W_in`` (``I |
  I + 2 G N | heads``); ``xBC_t = silu(b + sum_j w[:, j] xBC_{t-K+1+j})``
  depthwise, ``K = conv_kernel``, zeros before the sequence; ``[x | B |
  C] = xBC`` (``I | G x N | G x N``), head ``h`` reads ``B``, ``C`` of
  group ``h // (heads / G)``; ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)`` a head; ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``
  from ``S = 0``, ``y_t = S_t C_t + D x_t``; ``g = y * silu(z)``; ``g`` in
  ``G`` groups of ``I / G`` channels, each over ITS OWN rms, times the
  gain ``[I]``; ``W_out``.
- ``E`` (experts in a latent): ``s = sigmoid(z W_g)`` over all
  ``router_experts``; the ``num_experts_per_tok`` are SELECTED by ``s +
  b`` and WEIGHTED by ``s`` without it, over their sum (+ 1e-6), times
  ``routed_scaling_factor``; ``u = z W_down`` (``moe_latent_size``);
  expert ``e``: ``f_e(u) = relu(u W1_e)^2 W2_e`` (no gate matrix); ``r =
  sum w_e f_e(u)`` over the selected experts THIS CHIP HOLDS
  (``experts_held``; the weights stay normalised over all the selected);
  ``out = r W_up + relu(z S_1)^2 S_2``, the shared expert on the
  un-projected ``z`` at weight 1.

Departures from the published implementation (the configuration's
``assumed`` lists them): no rotary in the attention; the normalisation's
1e-6; no clamp on ``dt``; the gate before the grouped norm;
``in_proj``'s column order; multi-token prediction is not part of the
forward pass; seeded weights in place of trained ones.

``served_gaps`` is what ``correct`` reads: a served answer against the
reference's logits over its history plus the tokens served.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f(a):
    return jnp.asarray(a).astype(F32)


def _eps(cfg):
    return float(cfg["layer_norm_epsilon"])


def rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f(gain)


def attention_op(lw, z, cfg):
    """Grouped-query attention over one sequence ``z [T, H]``: no
    rotary, no norm."""
    T = z.shape[0]
    nq, nkv, D = (int(cfg[k]) for k in (
        "num_attention_heads", "num_key_value_heads", "head_dim"))
    q = (z @ _f(lw["wq"])).reshape(T, nq, D)
    k = jnp.repeat((z @ _f(lw["wk"])).reshape(T, nkv, D), nq // nkv, axis=1)
    v = jnp.repeat((z @ _f(lw["wv"])).reshape(T, nkv, D), nq // nkv, axis=1)
    # ptpu: allow[unguarded-domain] — D is the static head size, never 0
    s = jnp.einsum("qhd,khd->hqk", q, k) * D ** -0.5
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v).reshape(T, nq * D) \
        @ _f(lw["wo"])


def round_bf16(a):
    """To bfloat16 and back: what keeping ``a`` in the weights' dtype
    would leave of it (``reduce_precision``: a pair of casts outside a
    kernel is excess precision XLA may keep)."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def mamba_op(lw, z, cfg, round_state=None):
    """An ``M`` layer over one sequence ``z [T, H]``, the recurrence
    token by token; ``round_state``: what every token's new state goes
    through (the ``state_bf16`` control; ``None``: nothing)."""
    T = z.shape[0]
    nh, dh, N, G = (int(cfg[k]) for k in (
        "mamba_num_heads", "mamba_head_dim", "ssm_state_size", "n_groups"))
    I, K = nh * dh, int(cfg["conv_kernel"])
    C = I + 2 * G * N
    zxd = z @ _f(lw["w_in"])
    gate, raw, dt = zxd[:, :I], zxd[:, I:I + C], zxd[:, I + C:]
    rp = jnp.concatenate([jnp.zeros((K - 1, C), F32), raw])
    w = _f(lw["conv_w"])
    xbc = jax.nn.silu(_f(lw["conv_b"])
                      + sum(w[:, j] * rp[j:j + T] for j in range(K)))
    x = xbc[:, :I].reshape(T, nh, dh)

    def by_head(v):  # [T, G x N] -> [T, heads, N]: a head's group's
        return jnp.repeat(v.reshape(T, G, N), nh // G, axis=1)

    dt = jax.nn.softplus(dt + _f(lw["dt_bias"]))
    a = -jnp.exp(_f(lw["A_log"]))

    def token(S, t):  # S [heads, head_dim, N]
        x_t, b_t, c_t, dt_t = t
        S = jnp.exp(dt_t * a)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        if round_state is not None:
            S = round_state(S)
        return S, jnp.sum(S * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(
        token, jnp.zeros((nh, dh, N), F32),
        (x, by_head(xbc[:, I:I + G * N]), by_head(xbc[:, I + G * N:]), dt))
    y = y + _f(lw["D"])[:, None] * x
    g = (y.reshape(T, I) * jax.nn.silu(gate)).reshape(T, G, I // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                          + _eps(cfg))
    return (g.reshape(T, I) * _f(lw["ssm_norm"])) @ _f(lw["w_out"])


def routing(lw, z, cfg):
    """The dense ``[T, router_experts]`` matrix of routing weights (zero
    where an expert is not selected)."""
    E, k = int(cfg["router_experts"]), int(cfg["num_experts_per_tok"])
    s = jax.nn.sigmoid(z @ _f(lw["gate"]))
    _, sel = jax.lax.top_k(s + _f(lw["gate_bias"]), k)
    w = jnp.take_along_axis(s, sel, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    w = w * float(cfg["routed_scaling_factor"])
    return jnp.sum(jax.nn.one_hot(sel, E, dtype=F32) * w[..., None], axis=1)


def relu2_ff(w1, w2, z):
    """``relu(z W_1)^2 W_2``: two matrices, no gate."""
    return jnp.square(jax.nn.relu(z @ _f(w1))) @ _f(w2)


def experts_op(lw, z, cfg):
    """An ``E`` layer over tokens ``z [T, H]``: the held experts one
    after the other on every token's latent (``lw['w1'][i]`` is expert
    ``experts_held[i]``'s), through ``W_up`` once, plus the shared
    expert."""
    weights = routing(lw, z, cfg)[:, jnp.asarray(list(cfg["experts_held"]))]
    u = z @ _f(lw["w_down"])

    def one(out, expert):
        w1, w2, w = expert
        return out + w[:, None] * relu2_ff(w1, w2, u), None

    r, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        (lw["w1"], lw["w2"], weights.T))
    return r @ _f(lw["w_up"]) + relu2_ff(lw["s1"], lw["s2"], z)


def layer(lw, l, x, cfg, round_state=None):
    """Layer ``l``, ONE sub-block, over one sequence ``x [T, H]``."""
    letter = cfg["hybrid_override_pattern"][l]
    with jax.default_matmul_precision("highest"):
        if letter == "E":
            return x + experts_op(lw, rms(x, lw["ff_norm"], _eps(cfg)), cfg)
        z = rms(x, lw["op_norm"], _eps(cfg))
        if letter == "M":
            return x + mamba_op(lw, z, cfg, round_state)
        if letter == "*":
            return x + attention_op(lw, z, cfg)
    raise ValueError(f"pattern letter {letter!r}")


def embed(weights, tokens, cfg=None):
    return _f(weights["embed"])[jnp.asarray(tokens)]


def head(weights, x, cfg):
    with jax.default_matmul_precision("highest"):
        return rms(x, weights["norm_out"], _eps(cfg)) @ _f(weights["head"]).T


def whole(cfg: dict) -> dict:
    """``cfg`` with the share spelled out: a configuration that holds
    every expert gives no ``router_experts`` and no ``experts_held``."""
    E = int(cfg.get("router_experts", cfg["n_routed_experts"]))
    return {**cfg, "router_experts": E,
            "experts_held": list(cfg.get("experts_held") or range(E))}


def forward(weights, tokens, cfg):
    """Logits ``[T, V]`` of one sequence of token ids."""
    cfg = whole(cfg)
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
