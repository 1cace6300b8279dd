"""Bytes and operations the generative programs NEED for a
``granitemoehybrid`` configuration (state-space layers beside a few
grouped-query ones, dense feed-forwards), from its published keys and
the traffic's sizes alone (as ``required_xing.py`` does for
``xing4_0``): the MODEL's work and not a kernel's, so that another
implementation of the scan or of the step is read against the same
yardstick, and a roofline share says how close a program's time is to
the least the chip could take for the work that has to be done.

A matrix of ``p`` parameters costs ``2 p`` operations a token and, read
once, ``p x bytes`` (``bytes`` 2: bfloat16). Counted per layer:

- a ``mamba`` mixer (``I = mamba_n_heads x mamba_d_head``, ``N =
  mamba_d_state``): ``W_in`` ``H x (2 I + 2 N + heads)`` and ``W_out``
  ``I x H``; its vectors (conv taps and bias, ``A_log``, ``dt_bias``,
  ``D``, the gated norm's gain) are float32 and count 4 bytes each;
- the scan, the published chunked form at chunks of ``Q =
  mamba_chunk_size``: a token sees the ``s <= t`` of its OWN row inside
  its chunk (``pairs``: the rows' chunks' ``q (q + 1) / 2`` summed, a
  row chunked from its own first token), and a pair costs ``2 N`` for
  ``C . B`` ONCE for all heads plus ``2 head_dim`` a head for the
  product with the channels; between chunks ``2 N I`` a token for ``C
  S`` and ``2 N I`` for the state's update. Every input is read once
  (``x``, ``B``, ``C`` in the weights' dtype, ``dt`` float32 a head),
  ``y`` written once, and a row's float32 state written once;
- a decode step of a ``mamba`` layer reads and writes a row's float32
  state ``N x I`` once each, and costs ``5 N I`` operations a row
  (decay, outer product and sum for the update, product and sum for the
  read-out);
- an ``attention`` layer: ``W_q``, ``W_k``, ``W_v``, ``W_o``; a
  prefill's query costs ``4 head_dim`` operations a head a key it sees;
  a decode step reads ``2 kv_heads head_dim`` cache elements a cached
  token and costs ``4 head_dim`` a head a cached token;
- a feed-forward ``3 H shared_intermediate_size``;
- the head is the embedding (tied), ``V x H``, read whole by every
  decode step and once by a prefill (each row's last token).
"""

from __future__ import annotations


def _sizes(cfg: dict) -> dict:
    H, nq = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    nkv = int(cfg["num_key_value_heads"])
    D = int(cfg.get("head_dim") or H // nq)
    nh, dh, N = (int(cfg[k]) for k in ("mamba_n_heads", "mamba_d_head",
                                       "mamba_d_state"))
    I, K = nh * dh, int(cfg["mamba_d_conv"])
    kinds = list(cfg["layer_types"])
    n_mamba, n_attn = kinds.count("mamba"), kinds.count("attention")
    if n_mamba + n_attn != int(cfg["num_hidden_layers"]):
        raise ValueError("layer_types names other kinds than mamba and "
                         "attention, or not num_hidden_layers of them")
    mamba = H * (2 * I + 2 * N + nh) + I * H
    attn = H * nq * D + 2 * H * nkv * D + nq * D * H
    ff = 3 * H * int(cfg["shared_intermediate_size"])
    return {
        "H": H, "I": I, "N": N, "heads": nh, "head_dim": dh,
        "chunk": int(cfg["mamba_chunk_size"]),
        "n_mamba": n_mamba, "n_attn": n_attn,
        "mamba": mamba, "attn": attn, "ff": ff,
        # float32: conv taps and bias over x B C, three vectors a head,
        # the gated norm's gain; the two norms of every layer
        "vectors": n_mamba * ((I + 2 * N) * (K + 1) + 3 * nh + I)
        + (n_mamba + n_attn) * 2 * H + H,
        "matrices": n_mamba * mamba + n_attn * attn + (n_mamba + n_attn) * ff,
        "head": int(cfg["vocab_size"]) * H,
        "q_heads": nq, "kv_width": 2 * nkv * D, "D": D,
        "conv_window": (K - 1) * (I + 2 * N),
    }


def chunk_pairs(length: int, chunk: int) -> int:
    """The same-row causal pairs inside chunks of one row, chunked from
    its own first token: ``q (q + 1) / 2`` a chunk of ``q`` tokens."""
    full, rest = divmod(int(length), int(chunk))
    return full * chunk * (chunk + 1) // 2 + rest * (rest + 1) // 2


def ssm_scan(cfg: dict, rows: float, tokens: float, scan_pairs: float,
             weight_bytes: int = 2) -> dict:
    """The scans of one ``_gen_prefill`` dispatch (every ``mamba``
    layer) over ``tokens`` real tokens of ``rows`` rows whose chunks
    hold ``scan_pairs`` same-row causal pairs."""
    s = _sizes(cfg)
    ops = scan_pairs * (2 * s["N"] + 2 * s["I"]) \
        + tokens * 4 * s["N"] * s["I"]
    io = tokens * ((2 * s["I"] + 2 * s["N"]) * weight_bytes
                   + s["heads"] * 4) + rows * s["N"] * s["I"] * 4
    return {"bytes": float(s["n_mamba"] * io),
            "ops": float(s["n_mamba"] * ops)}


def ssm_step(cfg: dict, rows: float, steps: int) -> dict:
    """The state updates and read-outs of one ``_gen_decode`` dispatch:
    ``steps`` steps of ``rows`` rows through every ``mamba`` layer."""
    s = _sizes(cfg)
    state = s["N"] * s["I"]
    return {"bytes": float(steps * rows * s["n_mamba"] * 2 * state * 4),
            "ops": float(steps * rows * s["n_mamba"] * 5 * state)}


def gen_prefill(cfg: dict, rows: float, tokens: float, pairs: float,
                scan_pairs: float, weight_bytes: int = 2) -> dict:
    """One ``_gen_prefill`` dispatch over ``rows`` histories of ``tokens``
    REAL tokens in all (pad slots need nothing); ``pairs``: the (query,
    key) pairs the attention layers' queries see (the rows' ``n (n + 1)
    / 2`` summed). The head runs on each row's last token only. Every
    weight is read once; the attention layers' keys and values and the
    ``mamba`` layers' states and conv windows are written."""
    s = _sizes(cfg)
    scan = ssm_scan(cfg, rows, tokens, scan_pairs, weight_bytes)
    ops = 2 * tokens * s["matrices"] + 2 * rows * s["head"] \
        + s["n_attn"] * s["q_heads"] * 4 * s["D"] * pairs + scan["ops"]
    left = tokens * s["n_attn"] * s["kv_width"] * weight_bytes \
        + rows * s["n_mamba"] * (s["N"] * s["I"] + s["conv_window"]) * 4
    return {"bytes": float((s["matrices"] + s["head"]) * weight_bytes
                           + s["vectors"] * 4 + left),
            "ops": float(ops)}


def gen_decode(cfg: dict, rows: float, steps: int, cache: float,
               weight_bytes: int = 2) -> dict:
    """One ``_gen_decode`` dispatch: ``steps`` forward passes of ``rows``
    tokens. A step reads every weight once (the head too), reads and
    writes each row's recurrent states and conv windows, and reads each
    row's keys and values: ``cache`` tokens an attention layer (the mean
    history and what has been generated so far)."""
    s = _sizes(cfg)
    step = ssm_step(cfg, rows, 1)
    step_bytes = (s["matrices"] + s["head"]) * weight_bytes \
        + s["vectors"] * 4 + step["bytes"] \
        + rows * s["n_mamba"] * 2 * s["conv_window"] * 4 \
        + rows * s["n_attn"] * cache * s["kv_width"] * weight_bytes
    token_ops = 2 * (s["matrices"] + s["head"]) \
        + s["n_attn"] * s["q_heads"] * 4 * s["D"] * cache
    return {"bytes": float(steps * step_bytes),
            "ops": float(steps * (rows * token_ops + step["ops"]))}
