"""Bytes and operations the generative programs NEED for a ``xing4_0``
configuration (latent attention inside ``hc_mult`` residual streams),
from its published keys and the traffic's sizes alone (as
``required_laguna.py`` does for ``laguna``): not what the device
executes (pad slots, tiles of a mask computed and thrown away, keys and
values laid out from the latents), so a roofline share says how close a
program's time is to the least the chip could take for the work that
has to be done.

A matrix of ``p`` parameters costs ``2 p`` operations a token and, read
once, ``p x bytes`` (``bytes`` 2: bfloat16). Counted per layer:

- latent attention: ``W_qa`` ``H x q_rank``, ``W_qb`` ``q_rank x heads
  (nope + rope)``, ``W_kva`` ``H x (kv_rank + rope)``, ``W_kvb``
  ``kv_rank x heads (nope + v)``, ``W_o`` ``heads v x H``. A prefill's
  query costs ``2 (nope + rope) + 2 v`` operations a head a key it sees
  (the expanded form). A decode step's costs ``2 (kv_rank + rope) + 2
  kv_rank`` a head a cached token (the absorbed form: the scores over
  the latent beside the rotated key, the weighted sum of latents), and
  reads ``kv_rank + rope`` cache elements a token ONCE for all heads;
- the residual path, twice a layer (both sub-blocks): the projection
  ``n H x (2 n + n^2)`` in float32 (4 bytes), ``2 H (n^2 + 2 n)``
  operations a token for the mix, the read and the write, and in a
  prefill the ``n`` float32 streams read once and written once;
- dense feed-forward ``3 H I``; one expert ``3 H F``, ``k`` of them a
  token, plus the shared experts ``3 H F n_shared``; the router ``H x
  E``;
- the head ``V x H`` is its own matrix (untied), read whole by every
  decode step; the embedding is read a row a token.
"""

from __future__ import annotations


def _sizes(cfg: dict) -> dict:
    H, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    rq, rkv = int(cfg["q_lora_rank"]), int(cfg["kv_lora_rank"])
    dn, dr, dv = (int(cfg[k]) for k in ("qk_nope_head_dim",
                                        "qk_rope_head_dim", "v_head_dim"))
    n, layers = int(cfg.get("hc_mult") or 1), int(cfg["num_hidden_layers"])
    dense = min(int(cfg["first_k_dense_replace"]), layers)
    F = int(cfg["moe_intermediate_size"])
    attn = H * rq + rq * heads * (dn + dr) + H * (rkv + dr) \
        + rkv * heads * (dn + dv) + heads * dv * H
    # float32 parameters of one sub-block's coefficients
    hc = n * H * (2 * n + n * n) if n > 1 else 0
    sparse = H * int(cfg["n_routed_experts"]) \
        + 3 * H * F * int(cfg.get("n_shared_experts") or 0)
    outside = layers * attn + dense * 3 * H * int(cfg["intermediate_size"]) \
        + (layers - dense) * sparse
    return {
        "H": H, "heads": heads, "n": n, "layers": layers,
        "outside": outside, "hc": 2 * layers * hc,
        "mix_ops": 2 * layers * 2 * H * (n * n + 2 * n) if n > 1 else 0,
        "n_expert_layers": layers - dense, "expert": 3 * H * F,
        "n_experts": int(cfg["n_routed_experts"]),
        "k": int(cfg["num_experts_per_tok"]),
        "head": int(cfg["vocab_size"]) * H,
        "pair_ops": 2 * (dn + dr) + 2 * dv,
        "cached_ops": 2 * (rkv + dr) + 2 * rkv,
        "latent": rkv + dr, "qk": dn + dr, "v": dv,
    }


def attn_prefill(cfg: dict, pairs: float, tokens: float,
                 weight_bytes: int = 2) -> dict:
    """The attention kernel's calls of one ``_gen_prefill`` dispatch
    (every layer): ``2 (nope + rope) + 2 v`` operations a head a pair a
    query sees (``pairs``: the rows' ``len (len + 1) / 2`` summed), and
    the real tokens' queries, keys and values read and outputs written
    once a layer."""
    s = _sizes(cfg)
    ops = s["layers"] * s["heads"] * s["pair_ops"] * pairs
    io = tokens * s["layers"] * s["heads"] * 2 * (s["qk"] + s["v"]) \
        * weight_bytes
    return {"bytes": float(io), "ops": float(ops)}


def gen_prefill(cfg: dict, rows: float, tokens: float, pairs: float,
                weight_bytes: int = 2) -> dict:
    """One ``_gen_prefill`` dispatch over ``rows`` histories of ``tokens``
    REAL tokens in all (pad slots need nothing). The head runs on each
    row's last token only. Every weight is read once, the experts' too
    (a prefill of thousands of tokens touches them all), every layer's
    latents are written, and the float32 streams go once in and once out
    a sub-block."""
    s = _sizes(cfg)
    all_experts = s["n_expert_layers"] * s["n_experts"] * s["expert"]
    ops = (2 * tokens * (s["outside"] + s["hc"]
                         + s["n_expert_layers"] * s["k"] * s["expert"])
           + tokens * s["mix_ops"] + 2 * rows * s["head"]
           + attn_prefill(cfg, pairs, tokens)["ops"])
    cache = s["layers"] * tokens * s["latent"] * weight_bytes
    streams = 2 * s["layers"] * 2 * tokens * s["n"] * s["H"] * 4 \
        if s["n"] > 1 else 0
    return {"bytes": float((s["outside"] + s["head"] + all_experts)
                           * weight_bytes + s["hc"] * 4 + cache + streams),
            "ops": float(ops)}


def gen_decode(cfg: dict, rows: float, steps: int, experts_touched: float,
               cache: float, weight_bytes: int = 2) -> dict:
    """One ``_gen_decode`` dispatch: ``steps`` forward passes of ``rows``
    tokens. A step reads every weight outside the routed experts and the
    head once, ``experts_touched`` experts a layer (the measured mean of
    distinct experts a step's rows selected: ``pio_moe_experts_touched``)
    and each row's latents: ``cache`` tokens a layer (the mean history
    and what has been generated so far), ``kv_rank + rope`` wide."""
    s = _sizes(cfg)
    step_bytes = ((s["outside"] + s["head"]) * weight_bytes + s["hc"] * 4
                  + s["n_expert_layers"] * experts_touched * s["expert"]
                  * weight_bytes
                  + rows * s["layers"] * cache * s["latent"] * weight_bytes)
    token_ops = (2 * (s["outside"] + s["hc"] + s["head"]
                      + s["n_expert_layers"] * s["k"] * s["expert"])
                 + s["mix_ops"]
                 + s["layers"] * s["heads"] * s["cached_ops"] * cache)
    return {"bytes": float(steps * step_bytes),
            "ops": float(steps * rows * token_ops)}
