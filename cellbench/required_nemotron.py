"""Bytes and operations the generative programs NEED for a ``nemotron_h``
configuration cut to a chip's share (layers of ONE sub-block each:
Mamba-2 mixers over several groups, a few grouped-query layers,
sparse-expert feed-forwards whose relu² experts work in a latent, of
which this chip holds ``n_routed_experts`` of ``router_experts``), from
its published keys and the traffic's sizes alone (as
``required_granite.py`` does for ``granitemoehybrid``): the MODEL's work
under the share and not a kernel's, so that another implementation of
the scan, the step or the expert product is read against the same
yardstick, and a roofline share says how close a program's time is to
the least the chip could take for the work that has to be done.

A matrix of ``p`` parameters costs ``2 p`` operations a token and, read
once, ``p x bytes`` (``bytes`` 2: bfloat16). Counted per layer:

- an ``M`` mixer (``I = mamba_num_heads x mamba_head_dim``, ``N =
  ssm_state_size``, ``G = n_groups``): ``W_in`` ``H x (2 I + 2 G N +
  heads)`` and ``W_out`` ``I x H``; its vectors (conv taps and bias,
  ``A_log``, ``dt_bias``, ``D``, the gated norm's gain) are float32 and
  count 4 bytes each;
- the scan, the published chunked form at chunks of ``Q = chunk_size``:
  a token sees the ``s <= t`` of its OWN row inside its chunk
  (``pairs``: the rows' chunks' ``q (q + 1) / 2`` summed, a row chunked
  from its own first token), and a pair costs ``2 N`` for ``C . B`` ONCE
  A GROUP (``G`` of them) plus ``2 head_dim`` a head for the product
  with the channels; between chunks ``2 N I`` a token for ``C S`` and
  ``2 N I`` for the state's update. Every input is read once (``x`` and
  a ``B`` and a ``C`` a group in the weights' dtype, ``dt`` float32 a
  head), ``y`` written once, and a row's float32 state written once;
- a decode step of an ``M`` layer reads and writes a row's float32 state
  ``N x I`` once each, and costs ``5 N I`` operations a row;
- a ``*`` layer: ``W_q``, ``W_k``, ``W_v``, ``W_o``; a prefill's query
  costs ``4 head_dim`` operations a head a key it sees; a decode step
  reads ``2 kv_heads head_dim`` cache elements a cached token and costs
  ``4 head_dim`` a head a cached token;
- an ``E`` layer outside its routed experts, once a token and layer: the
  router ``H x router_experts`` (float32 scores), the latent's
  down-projection ``H x L`` and up-projection ``L x H`` (``L =
  moe_latent_size``), the shared expert ``2 H S``;
- a routed expert is two matrices ``L x F`` and ``F x L`` (``F =
  moe_intermediate_size``, relu² between, no gate matrix): an ASSIGNMENT
  HELD (a token's selected expert that this chip holds) costs ``4 L F``
  operations; a decode step reads a held expert that at least one of its
  rows selected ONCE (``2 L F`` elements) and never one nobody selected;
  a prefill reads every held expert once;
- embedding and head are ``vocab_size x H`` each (the slice held); the
  head is read whole by every decode step and once by a prefill (each
  row's last token).
"""

from __future__ import annotations

from .required_granite import chunk_pairs  # noqa: F401 — the same chunks


def _sizes(cfg: dict) -> dict:
    H, nq = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    nkv, D = int(cfg["num_key_value_heads"]), int(cfg["head_dim"])
    nh, dh, N, G = (int(cfg[k]) for k in (
        "mamba_num_heads", "mamba_head_dim", "ssm_state_size", "n_groups"))
    I, K = nh * dh, int(cfg["conv_kernel"])
    pattern = cfg["hybrid_override_pattern"]
    n_m, n_a, n_e = (pattern.count(c) for c in "M*E")
    if n_m + n_a + n_e != int(cfg["num_hidden_layers"]):
        raise ValueError("hybrid_override_pattern names other letters than "
                         "M, * and E, or not num_hidden_layers of them")
    L, F = int(cfg["moe_latent_size"]), int(cfg["moe_intermediate_size"])
    S = int(cfg["moe_shared_expert_intermediate_size"])
    E = int(cfg.get("router_experts", cfg["n_routed_experts"]))
    xbc = I + 2 * G * N
    mamba = H * (I + xbc + nh) + I * H
    attn = H * nq * D + 2 * H * nkv * D + nq * D * H
    return {
        "H": H, "I": I, "N": N, "G": G, "heads": nh, "head_dim": dh,
        "chunk": int(cfg["chunk_size"]), "xbc": xbc,
        "n_m": n_m, "n_a": n_a, "n_e": n_e,
        "mamba": mamba, "attn": attn,
        # an E layer outside its routed experts: router, latent down and
        # up, the shared expert's two matrices
        "router": H * E, "latent": 2 * H * L, "shared": 2 * H * S,
        "expert": 2 * L * F, "held": int(cfg["n_routed_experts"]),
        "routed": E, "top_k": int(cfg["num_experts_per_tok"]),
        # float32: conv taps and bias over x B C, three vectors a head,
        # the gated norm's gain; ONE norm a layer and the last one; the
        # router's correction bias
        "vectors": n_m * (xbc * (K + 1) + 3 * nh + I)
        + (n_m + n_a + n_e) * H + H + n_e * E,
        "head": int(cfg["vocab_size"]) * H,
        "q_heads": nq, "kv_width": 2 * nkv * D, "D": D,
        "conv_window": (K - 1) * xbc,
    }


def _fixed(s: dict) -> int:
    """Parameters of every matrix but the routed experts', embedding and
    head: what every token goes through and every step reads."""
    return s["n_m"] * s["mamba"] + s["n_a"] * s["attn"] \
        + s["n_e"] * (s["router"] + s["latent"] + s["shared"])


def ssm_scan(cfg: dict, rows: float, tokens: float, scan_pairs: float,
             weight_bytes: int = 2, **_) -> dict:
    """The scans of one ``_gen_prefill`` dispatch (every ``M`` layer)
    over ``tokens`` real tokens of ``rows`` rows whose chunks hold
    ``scan_pairs`` same-row causal pairs."""
    s = _sizes(cfg)
    ops = scan_pairs * (2 * s["N"] * s["G"] + 2 * s["I"]) \
        + tokens * 4 * s["N"] * s["I"]
    io = tokens * ((2 * s["I"] + 2 * s["G"] * s["N"]) * weight_bytes
                   + s["heads"] * 4) + rows * s["N"] * s["I"] * 4
    return {"bytes": float(s["n_m"] * io), "ops": float(s["n_m"] * ops)}


def ssm_step(cfg: dict, rows: float, steps: int, **_) -> dict:
    """The state updates and read-outs of one ``_gen_decode`` dispatch:
    ``steps`` steps of ``rows`` rows through every ``M`` layer."""
    s = _sizes(cfg)
    state = s["N"] * s["I"]
    return {"bytes": float(steps * rows * s["n_m"] * 2 * state * 4),
            "ops": float(steps * rows * s["n_m"] * 5 * state)}


def moe_step(cfg: dict, rows: float, steps: int, experts_touched: float,
             held_share: float, weight_bytes: int = 2, **_) -> dict:
    """The routed experts' products of one ``_gen_decode`` dispatch:
    ``steps`` steps in each of which ``experts_touched`` HELD experts a
    layer (a mean) were selected by at least one row: each is read once;
    a row's ``top_k x held_share`` assignments held cost ``4 L F``."""
    s = _sizes(cfg)
    return {"bytes": float(steps * s["n_e"] * experts_touched
                           * s["expert"] * weight_bytes),
            "ops": float(steps * s["n_e"] * rows * s["top_k"] * held_share
                         * 2 * s["expert"])}


def moe_prefill(cfg: dict, tokens: float, held_share: float,
                weight_bytes: int = 2, **_) -> dict:
    """The routed experts' products of one ``_gen_prefill`` dispatch:
    every held expert read once a layer; ``tokens x top_k x held_share``
    assignments held a layer at ``4 L F`` each."""
    s = _sizes(cfg)
    return {"bytes": float(s["n_e"] * s["held"] * s["expert"]
                           * weight_bytes),
            "ops": float(s["n_e"] * tokens * s["top_k"] * held_share
                         * 2 * s["expert"])}


def gen_prefill(cfg: dict, rows: float, tokens: float, pairs: float,
                scan_pairs: float, held_share: float,
                weight_bytes: int = 2, **_) -> dict:
    """One ``_gen_prefill`` dispatch over ``rows`` histories of ``tokens``
    REAL tokens in all (pad slots need nothing); ``pairs``: the (query,
    key) pairs the attention layers' queries see. The head runs on each
    row's last token only. Every weight held is read once; the attention
    layers' keys and values and the ``M`` layers' states and conv windows
    are written."""
    s = _sizes(cfg)
    scan = ssm_scan(cfg, rows, tokens, scan_pairs, weight_bytes)
    experts = moe_prefill(cfg, tokens, held_share, weight_bytes)
    ops = 2 * tokens * _fixed(s) + 2 * rows * s["head"] \
        + s["n_a"] * s["q_heads"] * 4 * s["D"] * pairs + scan["ops"] \
        + experts["ops"]
    left = tokens * s["n_a"] * s["kv_width"] * weight_bytes \
        + rows * s["n_m"] * (s["N"] * s["I"] + s["conv_window"]) * 4
    return {"bytes": float((_fixed(s) + s["head"]) * weight_bytes
                           + experts["bytes"] + s["vectors"] * 4 + left),
            "ops": float(ops)}


def gen_decode(cfg: dict, rows: float, steps: int, cache: float,
               experts_touched: float, held_share: float,
               weight_bytes: int = 2, **_) -> dict:
    """One ``_gen_decode`` dispatch: ``steps`` forward passes of ``rows``
    tokens. A step reads every weight outside the routed experts once
    (the head too) and the held experts its rows selected, reads and
    writes each row's recurrent states and conv windows, and reads each
    row's keys and values: ``cache`` tokens an attention layer (the mean
    history and what has been generated so far)."""
    s = _sizes(cfg)
    step = ssm_step(cfg, rows, 1)
    experts = moe_step(cfg, rows, 1, experts_touched, held_share,
                       weight_bytes)
    step_bytes = (_fixed(s) + s["head"]) * weight_bytes \
        + s["vectors"] * 4 + experts["bytes"] + step["bytes"] \
        + rows * s["n_m"] * 2 * s["conv_window"] * 4 \
        + rows * s["n_a"] * cache * s["kv_width"] * weight_bytes
    token_ops = 2 * (_fixed(s) + s["head"]) \
        + s["n_a"] * s["q_heads"] * 4 * s["D"] * cache
    return {"bytes": float(steps * step_bytes),
            "ops": float(steps * (rows * token_ops + step["ops"]
                                  + experts["ops"]))}
