"""Bytes and operations the generative programs NEED, from the
configuration's published keys and the traffic's sizes alone (as
``required.py`` does for ALS): not what the device executes (padding,
row groups that stream the weights again, every expert computed for
every row), so a roofline share says how close a program's time is to
the least the chip could take for the work that has to be done.

A matrix of ``n`` parameters costs ``2 n`` operations a token and, read
once, ``n x bytes`` (``bytes`` 2: bfloat16). Counted per layer:

- conv: ``W_in`` ``H x 3H``, ``W_out`` ``H x H``, the kernel ``H x K``;
- attention: ``W_q``, ``W_o`` ``H x H``, ``W_k``, ``W_v`` ``H x kv D``;
  per query ``4 D heads`` operations a key it attends to;
- dense feed-forward ``3 H I``; one expert ``3 H F``, ``k`` of them a
  token; the gate ``H x E``;
- the head is the embedding ``V x H`` (tied), read whole by every step.
"""

from __future__ import annotations


def _sizes(cfg: dict) -> dict:
    H, K = int(cfg["hidden_size"]), int(cfg["conv_L_cache"])
    nq, nkv = int(cfg["num_attention_heads"]), \
        int(cfg["num_key_value_heads"])
    D = int(cfg.get("head_dim") or H // nq)
    kinds = list(cfg["layer_types"])
    nd = int(cfg["num_dense_layers"])
    conv = H * 3 * H + H * H + H * K
    attn = 2 * H * nq * D + 2 * H * nkv * D
    ops_layers = sum(conv if k == "conv" else attn for k in kinds)
    return {
        "H": H, "D": D, "nq": nq, "nkv": nkv,
        "n_attn": sum(k != "conv" for k in kinds),
        "n_expert_layers": len(kinds) - nd,
        "outside": ops_layers + nd * 3 * H * int(cfg["intermediate_size"])
        + (len(kinds) - nd) * H * int(cfg["num_experts"]),
        "expert": 3 * H * int(cfg["moe_intermediate_size"]),
        "k": int(cfg["num_experts_per_tok"]),
        "head": int(cfg["vocab_size"]) * H,
    }


def gen_decode(cfg: dict, rows: float, steps: int, experts_touched: float,
               history_mean: float, weight_bytes: int = 2) -> dict:
    """One ``_gen_decode`` dispatch: ``steps`` forward passes of ``rows``
    tokens. A step reads every weight outside the experts and the head
    once, ``experts_touched`` experts a layer (the measured mean of
    distinct experts a step's rows selected: ``pio_moe_experts_touched``)
    and each row's keys and values (its history and what it has
    generated so far, ``steps / 2`` on average)."""
    s = _sizes(cfg)
    cache = history_mean + steps / 2.0
    kv_row = s["n_attn"] * cache * 2 * s["nkv"] * s["D"] * weight_bytes
    step_bytes = ((s["outside"] + s["head"]) * weight_bytes
                  + s["n_expert_layers"] * experts_touched * s["expert"]
                  * weight_bytes + rows * kv_row)
    token_ops = (2 * (s["outside"] + s["head"]
                      + s["n_expert_layers"] * s["k"] * s["expert"])
                 + s["n_attn"] * 4 * s["nq"] * s["D"] * cache)
    return {"bytes": float(steps * step_bytes),
            "ops": float(steps * rows * token_ops)}


def gen_prefill(cfg: dict, rows: float, tokens: float,
                tokens_squared: float, weight_bytes: int = 2) -> dict:
    """One ``_gen_prefill`` dispatch over ``rows`` histories of
    ``tokens`` REAL tokens in all (pad slots need nothing;
    ``tokens_squared`` is the sum of the squared lengths, for causal
    attention's ``len^2 / 2`` pairs). The head runs on each row's last
    token only. Every weight is read once, the experts' too (a prefill
    of thousands of tokens touches them all)."""
    s = _sizes(cfg)
    all_experts = s["n_expert_layers"] * int(cfg["num_experts"]) \
        * s["expert"]
    ops = (2 * tokens * (s["outside"]
                         + s["n_expert_layers"] * s["k"] * s["expert"])
           + 2 * rows * s["head"]
           + s["n_attn"] * 4 * s["nq"] * s["D"] * tokens_squared / 2.0)
    kv = s["n_attn"] * tokens * 2 * s["nkv"] * s["D"] * weight_bytes
    return {"bytes": float((s["outside"] + s["head"] + all_experts)
                           * weight_bytes + kv),
            "ops": float(ops)}
