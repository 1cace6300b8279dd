"""The plain reference of ``models/decoder.py``: the full forward pass of
the block stack in straightforward ``jax.numpy``, float32, at
``highest`` matmul precision, one sequence at a time. No cache, no
batching, no padding, no kernels; the experts one after the other. Five
families' equations, told apart by the keys a configuration has.

``cfg`` is the published ``config.json`` as a dict (plus ``head_dim``
where the source gives none, and ``experts_held`` for a chip's share).
``weights`` is ``{"embed": [V, H], "norm_out": [H], ["head": [V, H],]
"layers": [layer dict, ...]}``, the tree ``decoder.init_weights`` makes;
they are widened to float32 where they are used, so bfloat16 weights
give the float32 result OF THOSE WEIGHTS.

The equations (``H`` hidden size, ``n`` RMSNorm with ``norm_eps`` or
``rms_norm_eps``):

- layer ``l``: ``h = x + op_l(n_op(x))``, ``y = h + ff_l(n_ff(h))``.
- ``conv``: ``[B, C, u] = split3(z W_in)``; ``v = B * u``; ``c_t =
  sum_j w[:, j] v_{t-K+1+j}`` per channel (depthwise, causal, ``K =
  conv_L_cache``, zeros before the sequence); ``out = (C * c) W_out``.
- ``full_attention`` and ``sliding_attention``: ``q``, ``k``, ``v`` by
  head, ``heads_l = num_attention_heads_per_layer[l]`` query heads
  (``num_attention_heads`` where the family has one count) over
  ``num_key_value_heads``: each key-value head serves ``heads_l /
  kv_heads`` consecutive query heads; RMSNorm over each head of ``q``
  and of ``k`` (own gains; ``qk_norm``); rotary (rotate-half) by the
  layer's KIND: ``rope_parameters[kind]`` where the family gives them
  (else ``rope_theta`` over the whole head) with ``rope_theta``,
  ``partial_rotary_factor`` (the first ``r D`` dimensions of a head are
  rotated, the rest pass through) and ``rope_type``: ``default``
  ``1 / theta^(2i/d)``; ``yarn`` the blend ``w_i / (factor theta^(2i/d))
  + (1 - w_i) / theta^(2i/d)`` with ``w`` a ramp from 0 at dimension
  ``low`` to 1 at ``high`` (the dimensions that turn ``beta_fast`` and
  ``beta_slow`` times over ``original_max_position_embeddings``), cos
  and sin times ``attention_factor``. Query ``i`` sees keys ``j <= i``,
  in a sliding layer only ``j > i - sliding_window``; softmax at scale
  ``head_dim ** -0.5``. ``gating``: each head's output times
  ``sigmoid(z W_g)``, one scalar a head (``head_gate``). ``out = attn
  W_o``.
- dense feed-forward (``mlp_layer_types[l] == "dense"``, or ``l <
  num_dense_layers``): ``(silu(z W_1) * z W_3) W_2``.
- expert block: ``s = sigmoid(z W_g)`` (``scores``); the top ``k`` of
  ``s + b`` are selected; their weights are ``s`` WITHOUT ``b``;
  ``norm_topk_prob``: ``w / (sum w + 1e-6)``; times
  ``routed_scaling_factor`` (``moe_routed_scaling_factor``); ``out =
  sum_e w_e (silu(z W_1e) * z W_3e) W_2e``. No capacity, no drop. A
  shared expert (``shared_expert_intermediate_size``) is one more dense
  feed-forward that every token takes at weight 1.
- ``logits = n_out(x) E^T`` with the embedding ``E`` (tied), or with
  the head's own matrix where ``tie_word_embeddings`` is false.
- ``latent_attention`` (a configuration with ``kv_lora_rank``; every
  layer), in the EXPANDED form: ``c_q = n_768(z W_qa)``; ``[q_nope |
  q_rope] = c_q W_qb`` a head; ``[c | r] = z W_kva``; ``c_kv =
  n_512(c)``; ``k_rope = rope(r)``, ONE for all heads; ``q_rope =
  rope(q_rope)``; ``[k_nope | v] = c_kv W_kvb`` a head; scores ``[q_nope
  | q_rope] . [k_nope | k_rope]`` at scale ``(nope + rope)^-0.5 m^2``
  with ``m = 0.1 mscale_all_dim ln(factor) + 1`` (``rope_scaling``,
  yarn; cos and sin take ``yarn(mscale) / yarn(mscale_all_dim)``);
  causal softmax; ``out = concat(p v) W_o``. Rotary is rotate-half over
  the ``rope`` dimensions with yarn's blended frequencies.
- hyper-connections (``hc_mult = n`` over 1), around BOTH sub-blocks of
  a layer: the stream is ``X [n, H]`` a token (the embedding ``n``
  times at the entry, the ``n`` streams summed before ``n_out``). A
  sub-block ``F`` has ``phi_pre``, ``phi_post [nH, n]``, ``phi_res [nH,
  n n]``, biases ``b_pre``, ``b_post [n]``, ``b_res [n, n]`` and scalars
  ``a``: ``x~ = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)``; ``H_pre =
  sigmoid(a_pre x~ phi_pre + b_pre)``; ``H_post = 2 sigmoid(a_post x~
  phi_post + b_post)``; ``M = exp(clip(a_res mat(x~ phi_res) + b_res,
  mhc_h_res_clamp_min, mhc_h_res_clamp_max))``, then
  ``hc_sinkhorn_iters`` times its rows over (their sums + ``hc_eps``),
  then its columns likewise: ``H_res``. ``u = H_pre X``; ``y = F(n(u))``;
  ``X' = H_res X + H_post^T y``.

- ``granitemoehybrid``: ``x_0 = embedding_multiplier E[tok]``; both
  sub-blocks' outputs times ``residual_multiplier`` before they are
  added; ``logits / logits_scaling``; no routed experts
  (``num_local_experts`` 0): every feed-forward is the dense one,
  ``shared_intermediate_size`` wide. ``attention`` layers
  (``position_embedding_type`` ``nope``): no rotary and no per-head
  norm, softmax at scale ``attention_multiplier``. ``mamba`` layers
  (``I = mamba_n_heads x mamba_d_head``, ``N = mamba_d_state``, ONE
  group): ``[z | xBC | dt] = u W_in`` (``I | I + 2 N | heads``); ``xBC_t
  = silu(b + sum_j w[:, j] xBC_{t-K+1+j})`` depthwise, causal, ``K =
  mamba_d_conv``, zeros before the sequence; ``[x | B | C] = xBC`` (``B``
  and ``C`` shared by the heads); ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)`` a head; ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``
  from ``S = 0``, TOKEN BY TOKEN (``lax.scan``), ``y_t = S_t C_t + D
  x_t``; ``g = y * silu(z)``; ``out = (g / rms(g over all I) * gain)
  W_out``.

- ``nemotron_h`` (a configuration with ``hybrid_override_pattern``):
  layer ``l`` is ONE sub-block by the pattern's letter, ``x' = x +
  F_l(n_l(x))`` with the one norm it has; logits through the untied
  head. ``*``: grouped-query attention, no rotary, no per-head norm,
  no bias, softmax at ``head_dim ** -0.5``. ``M``: the Mamba-2 mixer
  above with the family's own key names and ``G = n_groups`` groups:
  ``xBC = [x | B (G x N) | C (G x N)]``, head ``h`` reads ``B`` and ``C``
  of group ``h // (heads / G)``, and the gated ``g`` is normalised in
  ``G`` groups of ``I / G`` channels, each by ITS OWN rms, times the
  gain ``[I]``. ``E``: ``s = sigmoid(z W_g)`` over all the router's
  experts; the top ``k`` by ``s + b``, weighted by ``s`` without it over
  their sum, times ``routed_scaling_factor``; ``u = z W_down``
  (``moe_latent_size``); expert ``e``: ``relu(u W1_e)^2 W2_e`` (no gate
  matrix); ``out = (sum_e w_e f_e(u)) W_up + relu(z S_1)^2 S_2``. Under a
  share (``experts_held`` of ``router_experts``) the sum runs over the
  selected experts held; the weights stay normalised over all ``k``.

Departures from the published implementations, all listed in the
benchmark configurations' ``assumed``. ``lfm2_moe``: ``head_dim = hidden
/ heads`` (the source gives null), the tied head, a conv kernel exactly
``conv_L_cache`` wide. ``laguna``: three conventions its config does not
spell out, each ONE named argument below so that the other reading is a
one-line change (a third family follows below):
``head_gate="scalar"`` (one sigmoid scalar a head;
``"wide"``: one a channel, ``W_g [H, heads D]``), ``qk_norm=True``
(RMSNorm over each head of ``q`` and ``k`` before rotary) and
``scores="sigmoid"`` (with the selection bias and the normalised top
``k``; ``"softmax"``: softmax over the experts). ``xing4_0`` (latent
attention and hyper-connections): the ``n`` copies at the entry and the
sum at the exit; no gain in the stream's own norm (``phi`` holds it); a
Sinkhorn pass is the row step, then the column step; ``hc_eps`` both
under the norm's root and in the divisions; rotate-half inside the 64
rotated dimensions (the published interleaving is a permutation of
seeded columns); the routing's ``1e-6`` where the family writes
``1e-20``; the next-next-token module (``num_nextn_predict_layers``) is
not part of the forward pass. ``granitemoehybrid``: ``head_dim = hidden
/ heads`` (the source gives null), no clamp on ``dt`` (the family's
default limits are 0 and infinity), the gated norm over all ``I``
channels (one group). ``nemotron_h``: attention without rotary (the
row's ``rope_theta`` is read by nothing), the routing's ``1e-6``, no
clamp on ``dt``, the gate before the grouped norm, ``in_proj``'s column
order ``[z | x | B | C | dt]``; multi-token prediction
(``num_nextn_predict_layers``) is not part of the forward pass. All:
seeded weights in place of trained ones. ``cellbench/reference_lfm2.py`` and
``cellbench/reference_laguna.py`` are the benchmark's copies;
``tests/test_decoder.py`` holds them to identical outputs.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f(a):
    return jnp.asarray(a).astype(F32)


def _eps(cfg):
    return float(next(cfg[k] for k in ("rms_norm_eps", "norm_eps",
                                       "layer_norm_epsilon") if k in cfg))


def _letter(cfg, l):
    """Layer ``l``'s letter of a ``hybrid_override_pattern`` (``None``:
    a family whose layers have both sub-blocks)."""
    pattern = cfg.get("hybrid_override_pattern")
    return pattern[l] if pattern else None


def _heads(cfg, l):
    per_layer = cfg.get("num_attention_heads_per_layer")
    return int(per_layer[l] if per_layer else cfg["num_attention_heads"])


def _is_dense(cfg, l):
    if _letter(cfg, l):  # "E": sparse experts; no other letter has one
        return False
    if "num_local_experts" in cfg:  # granitemoehybrid: 0 is all there is
        return int(cfg["num_local_experts"]) == 0
    kinds = cfg.get("mlp_layer_types")
    return kinds[l] == "dense" if kinds \
        else l < int(cfg["num_dense_layers"] if "num_dense_layers" in cfg
                     else cfg["first_k_dense_replace"])


def _kind(cfg, l):
    if _letter(cfg, l):
        return {"M": "mamba", "*": "full_attention"}[_letter(cfg, l)]
    kinds = cfg.get("layer_types")
    return kinds[l] if kinds else "latent_attention"


def _n_experts(cfg):
    """The router's outputs (``router_experts`` where the counting key
    gives a chip's share)."""
    return int(next(cfg[k] for k in ("router_experts", "num_experts",
                                     "n_routed_experts") if k in cfg))


def _shared_width(cfg):
    return int(cfg.get("shared_expert_intermediate_size")
               or cfg.get("moe_shared_expert_intermediate_size")
               or int(cfg.get("n_shared_experts") or 0)
               * int(cfg["moe_intermediate_size"]))


def _relu2(cfg):
    return cfg.get("mlp_hidden_act") == "relu2"


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
    kind = _kind(cfg, l)
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
    # ptpu: allow[unguarded-domain] — D is the static head size, never 0
    scale = float(cfg.get("attention_multiplier") or D ** -0.5)
    if cfg.get("position_embedding_type") != "nope" \
            and not cfg.get("hybrid_override_pattern"):
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
        s = jnp.einsum("qhd,khd->hqk", q, kk) * scale
        p = jax.nn.softmax(jnp.where(seen(at)[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, vv)
    else:
        bq = int(query_block)
        pad = -T % bq
        qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
            -1, bq, nkv, nq // nkv, D)

        def block(a):
            qs, i = a  # [bq, kv heads, heads of one, D], [bq]
            s = jnp.einsum("qgrd,kgd->grqk", qs, k) * scale
            p = jax.nn.softmax(jnp.where(seen(i), s, -jnp.inf), axis=-1)
            return jnp.einsum("grqk,kgd->qgrd", p, v)

        o = jax.lax.map(block, (qb, jnp.arange(T + pad).reshape(-1, bq))
                        ).reshape(T + pad, nq, D)[:T]
    if cfg.get("gating"):
        gate = jax.nn.sigmoid(z @ _f(lw["wg"]))
        o = o * gate[..., None] if head_gate == "scalar" \
            else o * gate.reshape(T, nq, D)
    return o.reshape(T, nq * D) @ _f(lw["wo"])


def mamba_op(lw, z, cfg, *, round_state=None):
    """A ``mamba`` layer over one sequence ``z [T, H]``, the recurrence
    token by token. ``round_state``: a function every token's new state
    goes through (the benchmark's ``state_bf16`` control rounds it to
    bfloat16; ``None``: float32 as it is)."""
    T = z.shape[0]

    def size(*names):  # one family's name for it or the other's
        return int(next(cfg[k] for k in names if k in cfg))

    nh, dh = size("mamba_n_heads", "mamba_num_heads"), \
        size("mamba_d_head", "mamba_head_dim")
    N, G = size("mamba_d_state", "ssm_state_size"), \
        int(cfg.get("mamba_n_groups", cfg.get("n_groups", 1)))
    I, K = nh * dh, size("mamba_d_conv", "conv_kernel")
    C = I + 2 * G * N
    zxd = z @ _f(lw["w_in"])
    gate, raw, dt = zxd[:, :I], zxd[:, I:I + C], zxd[:, I + C:]
    rp = jnp.concatenate([jnp.zeros((K - 1, raw.shape[1]), F32), raw])
    w = _f(lw["conv_w"])
    xbc = jax.nn.silu(_f(lw["conv_b"])
                      + sum(w[:, j] * rp[j:j + T] for j in range(K)))
    x = xbc[:, :I].reshape(T, nh, dh)
    dt = jax.nn.softplus(dt + _f(lw["dt_bias"]))
    a = -jnp.exp(_f(lw["A_log"]))

    def by_head(v):  # [T, G x N] -> [T, heads, N]: a head's group's
        return jnp.repeat(v.reshape(T, G, N), nh // G, axis=1)

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
    g = y.reshape(T, I) * jax.nn.silu(gate)
    # each group of channels over its own rms, then the gain [I]
    g = rms(g.reshape(T, G, I // G), 1.0, _eps(cfg)).reshape(T, I)
    return (g * _f(lw["ssm_norm"])) @ _f(lw["w_out"])


def yarn_mscale(scaling, key):
    """``0.1 scaling[key] ln(factor) + 1`` (yarn, a factor over 1)."""
    factor = float(scaling.get("factor", 1.0))
    if scaling.get("type") != "yarn" or factor <= 1:
        return 1.0
    # ptpu: allow[unguarded-domain] — factor is over 1 here
    return 0.1 * float(scaling.get(key, 1.0)) * math.log(factor) + 1.0


def latent_attention_op(lw, z, cfg, *, yarn_scale=True, query_block=None):
    """Latent attention over one sequence ``z [T, H]``, EXPANDED: every
    head's keys and values are laid out from the latents. ``yarn_scale
    =False`` leaves ``m^2`` off the softmax scale (what a test shows to
    differ). ``query_block``: queries that many at a time (the same
    numbers; scores ``[heads, block, T]`` and never ``[heads, T, T]``)."""
    T = z.shape[0]
    nq = int(cfg["num_attention_heads"])
    dn, dr, dv = (int(cfg[k]) for k in ("qk_nope_head_dim",
                                        "qk_rope_head_dim", "v_head_dim"))
    rkv = int(cfg["kv_lora_rank"])
    scaling = dict(cfg.get("rope_scaling") or {})
    rope = {**scaling, "rope_theta": cfg["rope_theta"],
            "rope_type": scaling.get("type", "default"),
            # ptpu: allow[unguarded-domain] — yarn_mscale is 1 or more
            "attention_factor": yarn_mscale(scaling, "mscale")
            / yarn_mscale(scaling, "mscale_all_dim")}
    cq = rms(z @ _f(lw["w_qa"]), lw["q_a_norm"], _eps(cfg))
    q = (cq @ _f(lw["w_qb"])).reshape(T, nq, dn + dr)
    kv = z @ _f(lw["w_kva"])
    c_kv = rms(kv[:, :rkv], lw["kv_a_norm"], _eps(cfg))
    k_rope = rotary(kv[:, None, rkv:], rope)          # [T, 1, rope]
    q = jnp.concatenate([q[..., :dn], rotary(q[..., dn:], rope)], axis=-1)
    up = (c_kv @ _f(lw["w_kvb"])).reshape(T, nq, dn + dv)
    k = jnp.concatenate([up[..., :dn],
                         jnp.broadcast_to(k_rope, (T, nq, dr))], axis=-1)
    v = up[..., dn:]
    m = yarn_mscale(scaling, "mscale_all_dim") if yarn_scale else 1.0
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
        return x + float(cfg.get("residual_multiplier", 1.0)) * fn(x)
    return hyper_connection(lw, sub, x, fn, cfg, iters=iters)


def dense_ff(lw, z, names=("w1", "w3", "w2")):
    w1, w3, w2 = (_f(lw[n]) for n in names)
    return (jax.nn.silu(z @ w1) * (z @ w3)) @ w2


def relu2_ff(w1, w2, z):
    """A feed-forward of two matrices: ``relu(z W_1)^2 W_2``."""
    return jnp.square(jax.nn.relu(z @ _f(w1))) @ _f(w2)


def route(lw, z, cfg, *, scores="sigmoid"):
    """The dense ``[T, E]`` matrix of routing weights (zero where an
    expert is not selected)."""
    E, k = _n_experts(cfg), int(cfg["num_experts_per_tok"])
    logits = z @ _f(lw["gate"])
    s = jax.nn.sigmoid(logits) if scores == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    biased = cfg.get("use_expert_bias", cfg.get("topk_method") == "noaux_tc"
                     or bool(cfg.get("hybrid_override_pattern")))
    pick = s + _f(lw["gate_bias"]) if biased else s
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
    held = cfg.get("experts_held") or range(_n_experts(cfg))
    weights = route(lw, z, cfg, scores=scores)[:, jnp.asarray(list(held))]
    if _relu2(cfg):  # the experts' rows are the layer's latents
        u = z @ _f(lw["w_down"]) if "w_down" in lw else z

        def plain(out, expert):
            w1, w2, w = expert
            return out + w[:, None] * relu2_ff(w1, w2, u), None

        out, _ = jax.lax.scan(plain, jnp.zeros_like(u),
                              (lw["w1"], lw["w2"], weights.T))
        return out @ _f(lw["w_up"]) if "w_up" in lw else out

    def one(out, expert):
        w1, w3, w2, w = expert
        y = (jax.nn.silu(z @ _f(w1)) * (z @ _f(w3))) @ _f(w2)
        return out + w[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(z),
                          (lw["w1"], lw["w3"], lw["w2"], weights.T))
    return out


def operator(lw, l, x, cfg, *, sinkhorn_iters=None, **how):
    """``h = x + op_l(n_op(x))`` over one sequence ``x [T, H]`` (``[T, n,
    H]`` and the hyper-connection under ``hc_mult``); ``how`` goes to
    the attention (:func:`attention_op`, :func:`latent_attention_op`)."""
    if _letter(cfg, l) == "E":  # the layer is its feed-forward alone
        return x

    def op(u):
        z = rms(u, lw["op_norm"], _eps(cfg))
        if _kind(cfg, l) == "conv":
            return conv_op(lw, z, cfg)
        if _kind(cfg, l) == "mamba":
            return mamba_op(lw, z, cfg)
        if _kind(cfg, l) == "latent_attention":
            return latent_attention_op(lw, z, cfg, **how)
        return attention_op(lw, z, cfg, l, **how)

    with jax.default_matmul_precision("highest"):
        return _around(lw, "op", x, op, cfg, sinkhorn_iters)


def feed_forward(lw, l, h, cfg, *, scores="sigmoid", sinkhorn_iters=None):
    """``y = h + ff_l(n_ff(h))`` over tokens ``h [T, H]`` (``[T, n, H]``
    under ``hc_mult``); every token on its own."""
    if _letter(cfg, l) in ("M", "*"):  # the layer is its mixer alone
        return h

    def ff(u):
        z = rms(u, lw["ff_norm"], _eps(cfg))
        if _is_dense(cfg, l):
            return dense_ff(lw, z)
        out = expert_ff(lw, z, cfg, scores=scores)
        if _shared_width(cfg):
            out = out + (relu2_ff(lw["s1"], lw["s2"], z) if _relu2(cfg)
                         else dense_ff(lw, z, ("s1", "s3", "s2")))
        return out

    with jax.default_matmul_precision("highest"):
        return _around(lw, "ff", h, ff, cfg, sinkhorn_iters)


def layer(lw, l, x, cfg):
    """Layer ``l`` over one sequence ``x [T, H]``."""
    return feed_forward(lw, l, operator(lw, l, x, cfg), cfg)


def embed(weights, tokens, cfg=None):
    return _f(weights["embed"])[jnp.asarray(tokens)] \
        * float((cfg or {}).get("embedding_multiplier", 1.0))


def streams_in(x, cfg):
    """``x [T, H]`` as the residual path takes it: ``hc_mult`` copies."""
    n = int(cfg.get("hc_mult") or 1)
    return x if n == 1 else jnp.repeat(x[:, None, :], n, axis=1)


def streams_out(x, cfg):
    """What the head reads: the ``hc_mult`` streams summed."""
    return x if int(cfg.get("hc_mult") or 1) == 1 else jnp.sum(x, axis=-2)


def head(weights, x, cfg):
    table = weights["embed"] if cfg.get("tie_word_embeddings", True) \
        else weights["head"]
    with jax.default_matmul_precision("highest"):
        return rms(x, weights["norm_out"], _eps(cfg)) @ _f(table).T \
            / float(cfg.get("logits_scaling", 1.0))


def forward(weights, tokens, cfg):
    """Logits ``[T, V]`` of one sequence of token ids."""
    x = streams_in(embed(weights, tokens, cfg), cfg)
    for l, lw in enumerate(weights["layers"]):
        x = layer(lw, l, x, cfg)
    return head(weights, streams_out(x, cfg), cfg)
