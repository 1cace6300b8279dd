"""Bytes and operations the generative programs NEED for a ``laguna``
configuration, from its published keys and the traffic's sizes alone
(as ``required_gen.py`` does for ``lfm2_moe``): not what the device
executes (pad slots, tiles of a mask that are computed and thrown away,
every expert computed for every row of a decode step), so a roofline
share says how close a program's time is to the least the chip could
take for the work that has to be done.

A matrix of ``n`` parameters costs ``2 n`` operations a token and, read
once, ``n x bytes`` (``bytes`` 2: bfloat16). Counted per layer ``l``:

- attention: ``W_q``, ``W_o`` ``H x heads_l D`` (``heads_l`` from
  ``num_attention_heads_per_layer``), ``W_k``, ``W_v`` ``H x kv D``, the
  head gate ``H x heads_l``; per query ``4 D heads_l`` operations a key
  it SEES: all before it in a full layer, at most ``sliding_window`` in
  a sliding one;
- dense feed-forward ``3 H I``; one expert ``3 H F``, ``k`` of them a
  token, plus the shared expert ``3 H S``; the router ``H x E``;
- the head ``V x H`` is its own matrix (untied), read whole by every
  decode step; the embedding is read a row a token.
"""

from __future__ import annotations


def _sizes(cfg: dict) -> dict:
    H, D = int(cfg["hidden_size"]), int(cfg["head_dim"])
    nkv = int(cfg["num_key_value_heads"])
    kinds, heads = list(cfg["layer_types"]), \
        list(cfg["num_attention_heads_per_layer"])
    sparse = [m == "sparse" for m in cfg["mlp_layer_types"]]
    gate = 1 if cfg.get("gating") else 0
    attn = [2 * H * h * D + 2 * H * nkv * D + gate * H * h for h in heads]
    shared = 3 * H * int(cfg.get("shared_expert_intermediate_size") or 0)
    outside = sum(attn) + sum(
        H * int(cfg["num_experts"]) + shared if s
        else 3 * H * int(cfg["intermediate_size"]) for s in sparse)
    return {
        "H": H, "D": D, "nkv": nkv, "outside": outside,
        "full_heads": [h for h, k in zip(heads, kinds)
                       if k == "full_attention"],
        "window_heads": [h for h, k in zip(heads, kinds)
                         if k == "sliding_attention"],
        "window": int(cfg.get("sliding_window") or 0),
        "n_expert_layers": sum(sparse),
        "expert": 3 * H * int(cfg["moe_intermediate_size"]),
        "k": int(cfg["num_experts_per_tok"]),
        "head": int(cfg["vocab_size"]) * H,
    }


def window_pairs(length, window: int):
    """(query, key) pairs of one sliding layer over a row of ``length``
    tokens: query ``p`` sees ``min(p + 1, window)`` keys."""
    short = min(length, window)
    return short * (short + 1) / 2.0 + max(length - window, 0) * window


def attn_prefill(cfg: dict, pairs_full: float, pairs_window: float,
                 tokens: float, weight_bytes: int = 2) -> dict:
    """The attention kernel's calls of one ``_gen_prefill`` dispatch
    (every attention layer): ``4 D heads_l`` operations a pair a query
    sees (``pairs_full``: the rows' ``len (len + 1) / 2`` summed;
    ``pairs_window``: their :func:`window_pairs`), and the real tokens'
    queries, keys and values read and outputs written once a layer."""
    s = _sizes(cfg)
    ops = 4 * s["D"] * (sum(s["full_heads"]) * pairs_full
                        + sum(s["window_heads"]) * pairs_window)
    heads = sum(s["full_heads"]) + sum(s["window_heads"])
    layers = len(s["full_heads"]) + len(s["window_heads"])
    io = tokens * s["D"] * weight_bytes * (2 * heads + 2 * layers * s["nkv"])
    return {"bytes": float(io), "ops": float(ops)}


def gen_prefill(cfg: dict, rows: float, tokens: float, pairs_full: float,
                pairs_window: float, weight_bytes: int = 2) -> dict:
    """One ``_gen_prefill`` dispatch over ``rows`` histories of ``tokens``
    REAL tokens in all (pad slots need nothing). The head runs on each
    row's last token only. Every weight is read once, the experts' too
    (a prefill of thousands of tokens touches them all), and every
    layer's keys and values are written."""
    s = _sizes(cfg)
    all_experts = s["n_expert_layers"] * int(cfg["num_experts"]) \
        * s["expert"]
    ops = (2 * tokens * (s["outside"]
                         + s["n_expert_layers"] * s["k"] * s["expert"])
           + 2 * rows * s["head"]
           + attn_prefill(cfg, pairs_full, pairs_window, tokens)["ops"])
    kv = (len(s["full_heads"]) + len(s["window_heads"])) * tokens * 2 \
        * s["nkv"] * s["D"] * weight_bytes
    return {"bytes": float((s["outside"] + s["head"] + all_experts)
                           * weight_bytes + kv),
            "ops": float(ops)}


def state_row(cfg: dict, cache_full: float, cache_window: float,
              weight_bytes: int = 2) -> float:
    """Bytes of keys and values one row's decode step reads: a full
    layer's whole cache, a sliding layer's ring up to the window."""
    s = _sizes(cfg)
    return (len(s["full_heads"]) * cache_full
            + len(s["window_heads"]) * cache_window) \
        * 2 * s["nkv"] * s["D"] * weight_bytes


def gen_decode(cfg: dict, rows: float, steps: int, experts_touched: float,
               cache_full: float, cache_window: float,
               weight_bytes: int = 2) -> dict:
    """One ``_gen_decode`` dispatch: ``steps`` forward passes of ``rows``
    tokens. A step reads every weight outside the routed experts and the
    head once, ``experts_touched`` experts a layer (the measured mean of
    distinct experts a step's rows selected: ``pio_moe_experts_touched``)
    and each row's keys and values: ``cache_full`` of them in a full
    layer (the mean history and what has been generated so far),
    ``cache_window`` in a sliding one (the same, held to the window)."""
    s = _sizes(cfg)
    step_bytes = ((s["outside"] + s["head"]) * weight_bytes
                  + s["n_expert_layers"] * experts_touched * s["expert"]
                  * weight_bytes
                  + rows * state_row(cfg, cache_full, cache_window,
                                     weight_bytes))
    token_ops = (2 * (s["outside"] + s["head"]
                      + s["n_expert_layers"] * s["k"] * s["expert"])
                 + 4 * s["D"] * (sum(s["full_heads"]) * cache_full
                                 + sum(s["window_heads"]) * cache_window))
    return {"bytes": float(steps * step_bytes),
            "ops": float(steps * rows * token_ops)}
