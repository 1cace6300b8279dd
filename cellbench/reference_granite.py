"""The benchmark's copy of the plain reference of the ``granitemoehybrid``
block stack (``predictionio_tpu/models/decoder_reference.py``; one test
holds the two to identical outputs). Nothing here imports the program,
and nothing is shared with its kernels: the state-space recurrence runs
TOKEN BY TOKEN (``lax.scan``), where the served prefill runs it in
chunks of 256 and the served decode one token a dispatched step.

The full forward pass in straightforward ``jax.numpy``, float32, at
``highest`` matmul precision, one sequence at a time: no cache, no
batching, no padding, no kernels, full causal attention. ``cfg`` is the
configuration file's dict with the family's published key names;
``weights`` is ``{"embed", "norm_out", "layers": [layer dict, ...]}``
and is widened to float32 where it is used, so the served bfloat16
weights give the float32 result OF THOSE WEIGHTS.

The equations (``n`` RMSNorm with its own gain, ``rms_norm_eps``):

- ``x_0 = embedding_multiplier E[tok]``; layer ``l``: ``h = x +
  residual_multiplier op_l(n(x))``, ``y = h + residual_multiplier
  ff(n(h))``; ``ff(z) = (silu(z W_1) * (z W_3)) W_2``,
  ``shared_intermediate_size`` wide (``[W_1 W_3]`` the published
  ``input_linear``; no routed experts: ``num_local_experts`` 0); logits
  ``n(x_L) E^T / logits_scaling``.
- ``attention``: 32 query heads over 8 key-value heads of 64, no rotary
  and no per-head norm (``position_embedding_type`` ``nope``), causal
  softmax of ``q k^T attention_multiplier``, ``W_o``.
- ``mamba`` (``I = mamba_n_heads x mamba_d_head``, ``N = mamba_d_state``,
  one group): ``[z | xBC | dt] = u W_in`` (``I | I + 2 N | heads``);
  ``xBC_t = silu(b + sum_j w[:, j] xBC_{t-K+1+j})`` depthwise, ``K =
  mamba_d_conv``, zeros before the sequence; ``[x | B | C] = xBC``; ``dt
  = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head; ``S_t =
  exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` from ``S = 0``, ``y_t = S_t C_t
  + D x_t``; ``g = y * silu(z)``; ``out = (g / rms(g over all I) * gain)
  W_out``.

Departures from the published implementation (the configuration's
``assumed`` lists them): ``head_dim = hidden / heads`` (the source gives
null); no clamp on ``dt`` (the family's default limits are 0 and
infinity); the gated norm over all ``I`` channels (one group); seeded
weights in place of trained ones.

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
    return float(cfg["rms_norm_eps"])


def rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f(gain)


def attention_op(lw, z, cfg):
    """Grouped-query attention over one sequence ``z [T, H]``: no
    rotary, no norm, the scale a published multiplier."""
    T = z.shape[0]
    nq, nkv = int(cfg["num_attention_heads"]), \
        int(cfg["num_key_value_heads"])
    D = int(cfg.get("head_dim") or cfg["hidden_size"] // nq)
    q = (z @ _f(lw["wq"])).reshape(T, nq, D)
    k = jnp.repeat((z @ _f(lw["wk"])).reshape(T, nkv, D), nq // nkv, axis=1)
    v = jnp.repeat((z @ _f(lw["wv"])).reshape(T, nkv, D), nq // nkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * float(cfg["attention_multiplier"])
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
    """A ``mamba`` layer over one sequence ``z [T, H]``, the recurrence
    token by token; ``round_state``: what every token's new state goes
    through (the ``state_bf16`` control; ``None``: nothing)."""
    T = z.shape[0]
    nh, dh, N = (int(cfg[k]) for k in ("mamba_n_heads", "mamba_d_head",
                                       "mamba_d_state"))
    I, K = nh * dh, int(cfg["mamba_d_conv"])
    zxd = z @ _f(lw["w_in"])
    gate, raw, dt = zxd[:, :I], zxd[:, I:2 * I + 2 * N], zxd[:, 2 * I + 2 * N:]
    rp = jnp.concatenate([jnp.zeros((K - 1, raw.shape[1]), F32), raw])
    w = _f(lw["conv_w"])
    xbc = jax.nn.silu(_f(lw["conv_b"])
                      + sum(w[:, j] * rp[j:j + T] for j in range(K)))
    x = xbc[:, :I].reshape(T, nh, dh)
    dt = jax.nn.softplus(dt + _f(lw["dt_bias"]))
    a = -jnp.exp(_f(lw["A_log"]))

    def token(S, t):  # S [heads, head_dim, N]
        x_t, b_t, c_t, dt_t = t
        S = jnp.exp(dt_t * a)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t
        if round_state is not None:
            S = round_state(S)
        return S, jnp.sum(S * c_t, axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((nh, dh, N), F32),
                        (x, xbc[:, I:I + N], xbc[:, I + N:], dt))
    y = y + _f(lw["D"])[:, None] * x
    g = y.reshape(T, I) * jax.nn.silu(gate)
    return rms(g, lw["ssm_norm"], _eps(cfg)) @ _f(lw["w_out"])


def operator(lw, l, x, cfg, round_state=None):
    """``h = x + residual_multiplier op_l(n_op(x))`` over one sequence
    ``x [T, H]``."""
    with jax.default_matmul_precision("highest"):
        z = rms(x, lw["op_norm"], _eps(cfg))
        out = mamba_op(lw, z, cfg, round_state) \
            if cfg["layer_types"][l] == "mamba" else attention_op(lw, z, cfg)
        return x + float(cfg["residual_multiplier"]) * out


def feed_forward(lw, l, h, cfg):
    """``y = h + residual_multiplier ff(n_ff(h))`` over tokens ``h [T,
    H]``; every token on its own."""
    with jax.default_matmul_precision("highest"):
        z = rms(h, lw["ff_norm"], _eps(cfg))
        ff = (jax.nn.silu(z @ _f(lw["w1"])) * (z @ _f(lw["w3"]))) \
            @ _f(lw["w2"])
        return h + float(cfg["residual_multiplier"]) * ff


def layer(lw, l, x, cfg):
    """Layer ``l`` over one sequence ``x [T, H]``."""
    return feed_forward(lw, l, operator(lw, l, x, cfg), cfg)


def embed(weights, tokens, cfg):
    return _f(weights["embed"])[jnp.asarray(tokens)] \
        * float(cfg["embedding_multiplier"])


def head(weights, x, cfg):
    with jax.default_matmul_precision("highest"):
        return rms(x, weights["norm_out"], _eps(cfg)) \
            @ _f(weights["embed"]).T / float(cfg["logits_scaling"])


def forward(weights, tokens, cfg):
    """Logits ``[T, V]`` of one sequence of token ids."""
    x = embed(weights, tokens, cfg)
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
