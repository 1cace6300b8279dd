"""A config-driven decoder block stack with generation, driven by the
keys of a published ``config.json``. Five families' names are read,
into ONE stack: ``nemotron_h`` (layers of ONE sub-block each, by the
letters of ``hybrid_override_pattern``: a Mamba-2 mixer over several
groups, grouped-query attention without rotary, or sparse experts with
a squared ReLU and no gate matrix that work in a LATENT narrower than
the stream, beside a shared expert; an untied head), ``lfm2_moe``
(short-convolution and grouped-query attention layers side by side,
dense and sparse-expert feed-forwards, a tied head),
``granitemoehybrid`` (Mamba-2 state-space layers beside
grouped-query layers without rotary, dense feed-forwards, four scalar
multipliers, a tied head), ``laguna`` (full and sliding-window attention layers side by
side with their own head counts and rotary, a sigmoid gate a head, a
shared expert beside the routed ones, an untied head) and ``xing4_0``
(latent attention in every layer: low-rank queries, a cache that holds a
token's normalised latent and ONE rotated key for all heads; ``hc_mult``
residual streams read, written and mixed by Sinkhorn-normalised
hyper-connections; a shared expert, an untied head).

Two jitted entry points, whose names the benchmark's metrics match in
the device trace: :func:`_gen_prefill` runs the histories of a batch and
leaves the per-sequence state on the device; :func:`_gen_decode` takes
that state (donated) and generates greedily, ``steps - 1`` forward
passes in one program (the first token is the prefill's). The host
sees one dispatch of two programs and syncs once, on the answer.

Layer ``l``: ``h = x + op_l(n(x))``, ``y = h + ff_l(n(h))`` with RMSNorm
``n`` (a layer of a ``hybrid_override_pattern`` is ONE of the two, with
the one norm and the one sub-block's weights it has: ``none`` stands in
``layer_types`` or ``mlp_layer_types`` for the sub-block a layer lacks);
``op_l`` by ``layer_types[l]``, one of five kinds (``conv``,
``mamba``, ``full_attention`` (``attention`` in one family's words),
``sliding_attention``, ``latent_attention``: every
layer of a family that gives ``kv_lora_rank`` and no ``layer_types``),
``ff_l`` dense where ``mlp_layer_types[l]`` is ``dense`` (``lfm2_moe``:
for ``l < num_dense_layers``; ``xing4_0``: ``first_k_dense_replace``)
and the expert block (``ops/moe.py``, plus the shared expert where the
family has one) elsewhere. ``models/decoder_reference.py`` writes the
equations out; the tests hold this module to it logit by logit.

The residual path has two forms, told apart when a program is traced
(:func:`_sub_block`). ``hc_mult`` absent or 1: the plain sums above over
one stream ``[T, H]``. ``hc_mult = n`` over 1: the stream is ``[n, T,
H]`` (the embedding ``n`` times at the entry, the ``n`` summed before
the head) and each of a layer's two sub-blocks ``F`` computes, from a
token's own streams, what it reads ``H_pre [n]``, writes ``H_post [n]``
and mixes ``H_res [n, n]`` (``hc_sinkhorn_iters`` row-then-column
normalisations of an exponential): ``u = H_pre X``, ``X' = H_res X +
H_post^T F(n(u))`` (:func:`_hc_coefficients`). A prefill's streams go
through two kernels a sub-block, one either side of ``F``
(``ops/hyper_mix.py``: the streams read twice and written once); a
decode step's few tokens through the same arithmetic as plain sums.

Layout. The prefill takes a batch PACKED: the real tokens of its rows
one row behind the other in one stream of ``T`` slots (``tokens [T]``,
``lengths [B]``), every row ENDING on a memory tile's edge
(:func:`row_ends`: at most a tile's slots less one lie spare before a
row's first token; the spare slots there and behind the last row hold
anything), so its cost follows the tokens a batch has and not rows x
its longest row. Whatever treats a token on its own (norms,
projections, feed-forwards, the router, the expert products) runs over
``[T, H]`` and knows no rows. The two operators that mix positions stay
inside a row: a conv tap that would reach before a row's first token
adds zero, and attention runs over the rows laid out right-aligned,
``[G, B, history, lanes]``, as the decode's cache has them anyway,
blockwise (``ops/window_attention.py``: causal, a window for sliding
layers, each row from its first real slot, tiles nobody sees skipped),
with rotary positions counted from a row's first token. Between the
projections and ``W_o`` nothing changes a layout: a head that fills
whole lane tiles stays in the lanes the matrix product wrote it to
(:func:`_groups`; its norm, rotary and gate by ``ops/head_lanes.py``),
and a row's tokens move between the stream and the rows whole tiles at
a time (:func:`_to_rows`: a row ends on a tile's edge in both). A spare
slot joins no expert's group and no row reads it: a row's logits do
not depend on
where in the stream it lies or on what lies beside it. A ``mamba``
layer's recurrence runs over the packed stream in chunks
(``ops/ssm_scan.py``) and restarts at each row's first token by the
rows' ids, wherever in a chunk that falls. State of five
kinds is carried from one program to the next: keys and values that
grow (full-attention layers), right-aligned at ``history`` slots
whatever the batch so that every row appends at the same slot and the
decode's shapes depend on ``B`` alone; a RING of ``sliding_window`` keys
and values (sliding layers: the token at position ``p`` lives in slot
``p mod window``, the prefill leaves a row's last ``window`` tokens
there and a decode step overwrites the oldest, so a step reads
``window`` slots whatever the history); a fixed ``conv_L_cache``-wide
window of ``B*u`` (conv layers); and LATENTS (latent-attention layers:
a token's normalised ``kv_lora_rank`` latent beside its rotated shared
key, ``[B, history + room, kv_lora_rank + rope]``, right-aligned like a
full cache; the prefill lays keys and values of every head out from the
tokens it has in hand, a decode step attends over the latents
themselves with the up-projections absorbed into the query and the
output, :func:`_latent_step`); and a RECURRENT state (``mamba`` layers:
each row's ``S [N, heads x head_dim]`` float32 after its last token
beside its last ``conv_L_cache - 1`` raw ``xBC``: 2 or 4 MiB a row and
layer at the published sizes WHATEVER the history, rewritten whole by
every decode step, :func:`_mamba_step`).

Precision. Weights in ``cfg.dtype`` (bfloat16 as served). Every matrix
product takes operands in that dtype and accumulates in float32
(``preferred_element_type``); the residual stream (all ``hc_mult`` of
them), the hyper-connections' coefficients (their projection at
``highest``, the sigmoids, the exponential and the Sinkhorn passes),
norms (the latents' too), rotary, softmax (running maximum, sum and
accumulator), the gates' sigmoids and sums, and the conv window are
float32; the latent cache is kept in the weights' dtype. A ``mamba``
layer's conv, ``dt``, decays, state, skip and gated norm are float32;
``x``, ``B`` and ``C`` enter the chunked scan's products in the
weights' dtype, and a decode step's update is float32 throughout.

Every layer holds its own arrays and the stack is unrolled. (Stacking
the periods of the layer pattern and scanning them compiles the period
once, but the compiler then materialises each scanned slice of a stack
that a custom call reads: a 704 MB copy of one layer's experts a layer
and a step, read off the compiled program, which would triple what a
decode step moves.)
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass, fields
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import head_lanes, hyper_mix, moe, ssm_scan
from ..ops.window_attention import BLOCK as ATTENTION_BLOCK, window_attention

CONV, ATTENTION, SLIDING = "conv", "full_attention", "sliding_attention"
LATENT, MAMBA = "latent_attention", "mamba"
#: in ``layer_types`` or ``mlp_layer_types``: the layer has no such
#: sub-block (and no norm or weight of one)
NONE = "none"
#: a ``hybrid_override_pattern``'s letters: the ONE sub-block of a layer.
#: ``-`` (the family's dense feed-forward layer) is not written
PATTERN = {"M": (MAMBA, NONE), "*": (ATTENTION, NONE),
           "E": (NONE, "sparse")}
#: published names of one family that mean a field named by the other
ALIASES = {"rms_norm_eps": "norm_eps",
           "moe_routed_scaling_factor": "routed_scaling_factor",
           "n_routed_experts": "num_experts",
           "first_k_dense_replace": "num_dense_layers",
           "mamba_d_conv": "conv_L_cache", "mamba_conv_bias": "conv_bias",
           # nemotron_h's names
           "layer_norm_epsilon": "norm_eps",
           "mamba_num_heads": "mamba_n_heads",
           "mamba_head_dim": "mamba_d_head",
           "ssm_state_size": "mamba_d_state", "n_groups": "mamba_n_groups",
           "conv_kernel": "conv_L_cache", "use_conv_bias": "conv_bias",
           "chunk_size": "mamba_chunk_size", "expand": "mamba_expand",
           "moe_shared_expert_intermediate_size":
               "shared_expert_intermediate_size"}
#: a layer kind by another family's name for it
KINDS = {"attention": ATTENTION}
#: keys that switch on mathematics nobody has written here: they are
#: accepted at the value that switches it off, and raise otherwise
#: (``conv_bias`` is written for ``mamba`` layers and raises beside a
#: ``conv`` one)
UNWRITTEN = {"attention_bias": False,
             "moe_apply_router_weight_on_input": False,
             "moe_router_logit_softcapping": 0,
             "n_group": 1, "topk_group": 1, "ep_size": 1,
             "moe_layer_freq": 1, "topk_method": "noaux_tc",
             "scoring_func": "sigmoid", "num_local_experts": 0,
             "mamba_proj_bias": False, "mlp_bias": False, "use_bias": False,
             "mamba_hidden_act": "silu"}


def _freeze(v):
    """Dicts and lists of a ``config.json`` as tuples: a configuration
    is a static argument of the jitted programs, so it is hashed."""
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    return v


@dataclass(frozen=True)
class DecoderConfig:
    """The published keys, by their published names, plus ``dtype`` and
    ``experts_held`` (which experts' weights this chip holds; ``None``:
    all). ``lfm2_moe`` gives ``num_dense_layers``, one head count and one
    ``rope_theta``; ``laguna`` gives ``mlp_layer_types``,
    ``num_attention_heads_per_layer``, ``sliding_window``,
    ``rope_parameters`` by layer kind, ``gating``,
    ``shared_expert_intermediate_size`` and ``tie_word_embeddings``;
    ``xing4_0`` gives no ``layer_types`` (``kv_lora_rank`` makes every
    layer ``latent_attention``), ``q_lora_rank``, ``kv_lora_rank``,
    ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
    ``rope_scaling`` (yarn, by the key ``type``), ``n_routed_experts``,
    ``first_k_dense_replace``, ``n_shared_experts`` (times
    ``moe_intermediate_size``: the shared width) and the residual
    path's ``hc_mult``, ``hc_sinkhorn_iters``, ``hc_eps``,
    ``mhc_h_res_clamp_min`` / ``_max``. ``granitemoehybrid`` gives
    ``layer_types`` of ``mamba`` and ``attention``, the ``mamba_*`` sizes
    (``mamba_d_conv`` and ``mamba_conv_bias`` are read as ``conv_L_cache``
    and ``conv_bias``), no routed experts (``num_local_experts`` 0: every
    layer's feed-forward is dense, ``shared_intermediate_size`` wide),
    ``position_embedding_type`` ``nope`` and four scalars:
    ``embedding_multiplier``, ``residual_multiplier`` (on both
    sub-blocks' outputs), ``attention_multiplier`` (the softmax scale, in
    place of ``head_dim ** -0.5``) and ``logits_scaling`` (a divisor).
    ``nemotron_h`` gives ``hybrid_override_pattern`` (a letter a layer,
    :data:`PATTERN`; its attention takes no rotary and no per-head norm:
    ``rope_theta`` is read by nothing), the Mamba-2 sizes by its own
    names (``mamba_num_heads``, ``mamba_head_dim``, ``ssm_state_size``,
    ``n_groups``, ``conv_kernel``, ``use_conv_bias``, ``chunk_size``),
    ``mlp_hidden_act`` ``relu2`` (a feed-forward ``relu(z W_1)^2 W_2``,
    two matrices and no gate), ``moe_latent_size`` (the routed experts'
    rows are ``z W_down``, one down- and one up-projection a layer
    around all of them) and ``moe_shared_expert_intermediate_size``.
    ``router_experts``: the router's outputs where the key that counts
    the experts gives this chip's SHARE (a benchmark configuration cut
    as the model-configs guide says: ``n_routed_experts`` 128 held of
    ``router_experts`` 512, ``experts_held`` naming which).
    ``UNWRITTEN`` lists the keys that raise at any value but the one
    that switches them off."""

    hidden_size: int
    intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    vocab_size: int
    moe_intermediate_size: int = 0
    num_experts: int = 0
    num_experts_per_tok: int = 0
    layer_types: Optional[Tuple[str, ...]] = None
    num_dense_layers: Optional[int] = None
    mlp_layer_types: Optional[Tuple[str, ...]] = None
    num_attention_heads_per_layer: Optional[Tuple[int, ...]] = None
    sliding_window: Optional[int] = None
    rope_parameters: Optional[tuple] = None
    gating: bool = False
    shared_expert_intermediate_size: int = 0
    tie_word_embeddings: bool = True
    conv_L_cache: int = 3
    conv_bias: bool = False
    attention_bias: bool = False
    moe_apply_router_weight_on_input: bool = False
    moe_router_logit_softcapping: float = 0
    norm_eps: float = 1e-5
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    rope_theta: float = 1e6
    head_dim: Optional[int] = None
    q_lora_rank: Optional[int] = None
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: Optional[int] = None
    qk_rope_head_dim: Optional[int] = None
    v_head_dim: Optional[int] = None
    rope_scaling: Optional[tuple] = None
    n_shared_experts: int = 0
    n_group: int = 1
    topk_group: int = 1
    ep_size: int = 1
    moe_layer_freq: int = 1
    topk_method: str = "noaux_tc"
    scoring_func: str = "sigmoid"
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    mamba_n_heads: Optional[int] = None
    mamba_d_head: Optional[int] = None
    mamba_d_state: Optional[int] = None
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    mamba_n_groups: int = 1
    mamba_proj_bias: bool = False
    mamba_hidden_act: str = "silu"
    hybrid_override_pattern: Optional[str] = None
    mlp_hidden_act: str = "silu"
    mlp_bias: bool = False
    use_bias: bool = False
    moe_latent_size: int = 0
    router_experts: Optional[int] = None
    num_local_experts: int = 0
    shared_intermediate_size: Optional[int] = None
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    logits_scaling: float = 1.0
    position_embedding_type: str = "rope"
    dtype: str = "bfloat16"
    experts_held: Optional[Tuple[int, ...]] = None

    def __post_init__(self):
        put = functools.partial(object.__setattr__, self)
        n = self.num_hidden_layers
        latent = (self.q_lora_rank, self.kv_lora_rank, self.qk_nope_head_dim,
                  self.qk_rope_head_dim, self.v_head_dim)
        if self.hybrid_override_pattern is not None:
            if self.layer_types or self.mlp_layer_types:
                raise ValueError("hybrid_override_pattern beside "
                                 "layer_types: the layers are given twice")
            for letter in self.hybrid_override_pattern:
                if letter not in PATTERN:
                    raise ValueError(
                        f"hybrid_override_pattern letter {letter!r}: not "
                        f"written here (only {sorted(PATTERN)} are; '-' is "
                        f"the family's dense feed-forward layer)")
            kinds = [PATTERN[c] for c in self.hybrid_override_pattern]
            put("layer_types", tuple(k for k, _ in kinds))
            put("mlp_layer_types", tuple(k for _, k in kinds))
            put("position_embedding_type", "nope")
        if self.router_experts is not None:
            if self.experts_held is None \
                    or len(self.experts_held) != self.num_experts:
                raise ValueError(
                    "router_experts: the key that counts the experts then "
                    "gives the share held, and experts_held names as many")
            put("num_experts", int(self.router_experts))
        if self.layer_types is None:
            if self.kv_lora_rank is None:
                raise ValueError("layer_types says which operator each "
                                 "layer has (only a family with "
                                 "kv_lora_rank has one kind throughout)")
            put("layer_types", (LATENT,) * n)
        put("layer_types", tuple(KINDS.get(k, k) for k in self.layer_types))
        if LATENT in self.layer_types:
            if None in latent:
                raise ValueError(
                    "latent_attention layers need q_lora_rank, "
                    "kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim and "
                    "v_head_dim")
            if self.head_dim is None:
                put("head_dim", self.qk_nope_head_dim + self.qk_rope_head_dim)
        put("rope_scaling", _freeze(self.rope_scaling))
        if not self.shared_expert_intermediate_size and self.n_shared_experts:
            put("shared_expert_intermediate_size",
                self.n_shared_experts * self.moe_intermediate_size)
        if self.hc_mult < 1 or self.hc_sinkhorn_iters < 1:
            raise ValueError("hc_mult and hc_sinkhorn_iters are 1 or more")
        if self.experts_held is not None:
            put("experts_held", tuple(int(e) for e in self.experts_held))
        if self.head_dim is None:
            put("head_dim", self.hidden_size // self.num_attention_heads)
        if self.mlp_layer_types is None:
            if self.num_dense_layers is None and self.num_experts:
                raise ValueError("one of num_dense_layers and "
                                 "mlp_layer_types says which layers are "
                                 "dense")
            dense = n if self.num_dense_layers is None \
                else self.num_dense_layers  # no experts: every layer
            put("mlp_layer_types", tuple(
                "dense" if l < dense else "sparse" for l in range(n)))
        put("mlp_layer_types", tuple(self.mlp_layer_types))
        put("num_attention_heads_per_layer", tuple(
            self.num_attention_heads_per_layer
            or (self.num_attention_heads,) * n))
        put("rope_parameters", _freeze(self.rope_parameters))
        for name in ("layer_types", "mlp_layer_types",
                     "num_attention_heads_per_layer"):
            if len(getattr(self, name)) != n:
                raise ValueError(f"{name} must name num_hidden_layers "
                                 f"layers")
        if set(self.layer_types) - {CONV, ATTENTION, SLIDING, LATENT, MAMBA,
                                    NONE}:
            raise ValueError(f"layer types {set(self.layer_types)}: only "
                             f"{CONV!r}, {ATTENTION!r}, {SLIDING!r}, "
                             f"{LATENT!r} and {MAMBA!r} are written")
        if set(self.mlp_layer_types) - {"dense", "sparse", NONE}:
            raise ValueError(f"mlp layer types {set(self.mlp_layer_types)}")
        if (NONE, NONE) in zip(self.layer_types, self.mlp_layer_types):
            raise ValueError("a layer has one sub-block at least")
        if NONE in self.layer_types + self.mlp_layer_types \
                and self.hc_mult > 1:
            raise ValueError("layers of one sub-block under "
                             "hyper-connections: not written here")
        if self.mlp_hidden_act not in ("silu", "relu2"):
            raise ValueError(f"mlp_hidden_act {self.mlp_hidden_act!r}: only "
                             f"'silu' (gated) and 'relu2' are written")
        if self.mlp_hidden_act == "relu2" and "dense" in self.mlp_layer_types:
            raise ValueError("mlp_hidden_act 'relu2' in a dense "
                             "feed-forward: not written here (only in the "
                             "experts and the shared expert)")
        if self.moe_latent_size and self.mlp_hidden_act != "relu2":
            raise ValueError("moe_latent_size around gated experts: not "
                             "written here (only with mlp_hidden_act "
                             "'relu2')")
        for key, off in UNWRITTEN.items():
            if getattr(self, key) != off:
                raise ValueError(f"{key}={getattr(self, key)!r}: not "
                                 f"written here (only {off!r} is)")
        if self.conv_bias and CONV in self.layer_types:
            raise ValueError("conv_bias=True: not written for conv layers "
                             "(only for mamba ones)")
        if MAMBA in self.layer_types:
            if None in (self.mamba_n_heads, self.mamba_d_head,
                        self.mamba_d_state) or self.mamba_n_heads \
                    * self.mamba_d_head != self.mamba_expand * self.hidden_size:
                raise ValueError(
                    "mamba layers need mamba_d_state and mamba_n_heads x "
                    "mamba_d_head = mamba_expand x hidden_size")
            if self.mamba_n_groups < 1 \
                    or self.mamba_n_heads % self.mamba_n_groups:
                raise ValueError(
                    f"mamba_n_groups {self.mamba_n_groups}: the "
                    f"{self.mamba_n_heads} mamba heads come in equal groups")
        if self.position_embedding_type not in ("rope", "nope"):
            raise ValueError(f"position_embedding_type "
                             f"{self.position_embedding_type!r}: only 'rope' "
                             f"and 'nope' are written")
        if self.nope and set(self.layer_types) & {SLIDING, LATENT}:
            raise ValueError("attention without rotary and without a "
                             "per-head norm ('nope') is written for full "
                             "layers only")
        if self.hc_mult > 1 and self.residual_multiplier != 1:
            raise ValueError("residual_multiplier under hyper-connections: "
                             "not written here")
        if SLIDING in self.layer_types and not self.sliding_window:
            raise ValueError("sliding_attention layers need sliding_window")
        if any(h % self.num_key_value_heads
               for h in self.num_attention_heads_per_layer):
            raise ValueError("query heads must divide by key-value heads")
        if not self.nope:
            for kind in set(self.layer_types) - {CONV, MAMBA, NONE}:
                self.rope(kind)  # raises on a rope_type nobody has written

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "DecoderConfig":
        """From a ``config.json``'s dict; keys that say nothing about
        the block (``model_type``, ``max_position_embeddings``, the
        benchmark's own notes) are left where they are."""
        names = {f.name for f in fields(cls)}
        out = {}
        for k, v in d.items():
            k = ALIASES.get(k, k)
            if k in names:
                if k in out and out[k] != v:
                    raise ValueError(f"{k} is given twice, differently")
                out[k] = v
        return cls(**out)

    @property
    def nope(self) -> bool:
        """Attention layers without rotary and without a per-head norm
        (``position_embedding_type`` ``nope``)."""
        return self.position_embedding_type == "nope"

    @property
    def dense_width(self) -> int:
        """A dense feed-forward's width: ``intermediate_size``, or the
        shared feed-forward's where a family names that one."""
        return self.shared_intermediate_size or self.intermediate_size

    @property
    def attention_scale(self) -> float:
        """The softmax scale of the grouped-query layers."""
        if self.attention_multiplier is not None:
            return float(self.attention_multiplier)
        # ptpu: allow[unguarded-domain] — a head is 1 wide or more
        return self.head_dim ** -0.5

    @property
    def n_held(self) -> int:
        return self.num_experts if self.experts_held is None \
            else len(self.experts_held)

    def rope(self, kind: str) -> Tuple[Tuple[float, ...], float]:
        """``(inverse frequencies, factor on cos and sin)`` of a layer
        kind: ``rope_parameters[kind]`` where the family gives them by
        kind, else ``rope_theta`` over the whole head. A latent layer
        rotates its ``qk_rope_head_dim`` dimensions by ``rope_scaling``
        (whose kind is the key ``type``); its yarn puts
        ``yarn(mscale) / yarn(mscale_all_dim)`` on cos and sin and the
        rest on the softmax (:meth:`latent_scale`)."""
        if kind == LATENT:
            p = dict(self.rope_scaling or ())
            # ptpu: allow[unguarded-domain] — _yarn_mscale is 1 or more
            on_cos = _yarn_mscale(p, "mscale") \
                / _yarn_mscale(p, "mscale_all_dim")
            rope_type = p.pop("type", None) or p.pop("rope_type", "default")
            return _inverse_frequencies(
                self.qk_rope_head_dim, float(self.rope_theta), rope_type,
                tuple(sorted(p.items())))[0], on_cos
        by_kind = dict(self.rope_parameters or ())
        if kind not in by_kind:
            return _inverse_frequencies(self.head_dim, float(self.rope_theta),
                                        "default", ())
        p = dict(by_kind[kind])
        rotated = int(self.head_dim * float(p.pop("partial_rotary_factor",
                                                  1.0)))
        return _inverse_frequencies(
            rotated, float(p.pop("rope_theta")),
            p.pop("rope_type", "default"), tuple(sorted(p.items())))


    @property
    def latent_scale(self) -> float:
        """A latent layer's softmax scale: ``(nope + rope) ** -0.5`` times
        ``yarn(mscale_all_dim) ** 2`` (0.14468 at 192 wide, factor 64)."""
        m = _yarn_mscale(dict(self.rope_scaling or ()), "mscale_all_dim")
        # ptpu: allow[unguarded-domain] — a head is 1 wide or more
        return self.head_dim ** -0.5 * m * m


def _yarn_mscale(p: dict, key: str) -> float:
    """``0.1 p[key] ln(factor) + 1`` under yarn with a factor over 1,
    else 1: the family's ``yarn_get_mscale``."""
    factor = float(p.get("factor", 1.0))
    if p.get("type", p.get("rope_type")) != "yarn" or factor <= 1:
        return 1.0
    # ptpu: allow[unguarded-domain] — factor is over 1 here
    return 0.1 * float(p.get(key, 1.0)) * math.log(factor) + 1.0


@functools.lru_cache(maxsize=None)
def _inverse_frequencies(rotated: int, theta: float, rope_type: str,
                         rest: tuple):
    """Rotary inverse frequencies over the first ``rotated`` of a head's
    dimensions. ``yarn`` (the HF rope utilities' blend): interpolated
    frequencies ``1 / (factor theta^(2i/d))`` below the correction range,
    the plain ones above it, a linear ramp between; the range is where a
    dimension turns ``beta_fast`` .. ``beta_slow`` times over the
    original context."""
    # ptpu: allow[unguarded-domain] — rotated is a static size, never 0
    plain = theta ** (-np.arange(0, rotated, 2, dtype=np.float64) / rotated)
    p = dict(rest)
    if rope_type == "default":
        return tuple(plain), 1.0
    if rope_type != "yarn":
        raise ValueError(f"rope_type {rope_type!r}: only 'default' and "
                         f"'yarn' are written")
    factor = float(p["factor"])
    ctx = float(p["original_max_position_embeddings"])

    # ptpu: allow[unguarded-domain] — a config's positive constants, here
    # and below (theta 5e5, factor 64, a context of thousands)
    per_turn = rotated / (2 * math.log(theta))

    def turns(n):  # the dimension that turns n times over ctx positions
        # ptpu: allow[unguarded-domain] — see above
        return per_turn * math.log(ctx / (n * 2 * math.pi))

    low = max(math.floor(turns(float(p.get("beta_fast", 32)))), 0)
    high = min(math.ceil(turns(float(p.get("beta_slow", 1)))), rotated - 1)
    high = high + 0.001 if low == high else high
    ramp = np.clip((np.arange(rotated // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    # ptpu: allow[unguarded-domain] — see above
    attention = p.get("attention_factor", 0.1 * math.log(factor) + 1.0)
    # ptpu: allow[unguarded-domain] — see above
    return tuple(plain / factor * ramp + plain * (1.0 - ramp)), attention


# -- weights ----------------------------------------------------------------

#: scales of the seeded weights (nothing is trained or imported here).
#: ``embed``: the embedding's standard deviation. Every matrix is normal
#: x 1/sqrt(fan-in), which maps a normalised input to an output of
#: about unit RMS; the matrices that write to the residual stream take
#: one more factor from LAYER 1 ON: ``op_out`` (a conv layer's
#: ``w_out``, an attention layer's ``wo``), ``dense_out`` and
#: ``expert_out`` (``w2``). Layer 0 stays at 1: it, and not the tied
#: embedding, is the stream the later layers add to (were the embedding
#: the stream, a token's own logit would stand 45 spreads above the
#: rest and greedy would repeat it). With every factor at 1 each
#: sublayer adds as much as the one before and every gated product
#: doubles what was rounded off earlier: bfloat16 operands then read
#: 6-9 % of a logit's spread after 14 layers and the experts carry a
#: tenth of the residual (my chip runs, PR 27). At these factors an
#: operator adds 0.08, a dense feed-forward 0.4 and an expert block 0.6
#: of layer 0's RMS: the stream grows slowly (1.1 to 2.4 over the 14
#: layers of the benchmark's cut), the gated operators amplify little,
#: and the expert blocks carry most of what is added.
#: The hyper-connections' (``hc_mult`` over 1): ``hc_phi`` the standard
#: deviation of a token's three dynamic terms ``x~ phi`` (``x~`` has
#: unit RMS, ``phi`` is normal x ``hc_phi`` / sqrt(fan-in)), ``hc_bias``
#: that of the static biases, ``hc_diag`` what ``b_res`` has on its
#: diagonal beside them, and every ``a`` is 1: ``H_res`` then leans on
#: the diagonal without being the identity and differs token to token.
#: A ``mamba`` layer's three vectors take the Mamba-2 conventions and no
#: factor: ``A_log = log(uniform(1, 16))``, ``dt_bias`` the inverse
#: softplus of a step drawn log-uniformly from 0.001 to 0.1, ``D`` 1 (a
#: head's decay a token then spans about 0.2 to 0.999); its conv's bias
#: is normal x ``conv_bias``, its conv's taps take ``conv_taps`` and the
#: ``dt`` columns of its in-projection ``dt_in``. At 1 and 1 the skip ``D
#: x`` carries half of a mixer's output and a token's own projection
#: moves its step ``e^+-1`` around the drawn one; a benchmark
#: configuration sets them so that the STATE carries the output and the
#: drawn steps stand (its file has the readings).
INIT = {"embed": 0.02, "op_out": 0.08, "dense_out": 0.67,
        "expert_out": 2.0, "shared_out": 1.0, "gate_bias": 0.01,
        "hc_phi": 0.5, "hc_bias": 0.5, "hc_diag": 1.5, "conv_bias": 0.1,
        "conv_taps": 1.0, "dt_in": 1.0}


def _layer_shapes(cfg: DecoderConfig, l: int) -> dict:
    """``{name: (shape, fan_in, factor)}`` of layer ``l``; fan-in 0 marks
    a gain (ones); ``factor`` names the entry of :data:`INIT` a matrix
    is scaled by beside 1/sqrt(fan-in) (``None``: none; ``(entry, first
    column)``: its columns from that one on)."""
    H, D = cfg.hidden_size, cfg.head_dim
    nq, nkv = cfg.num_attention_heads_per_layer[l], cfg.num_key_value_heads
    out = {sub + "_norm": ((H,), 0, None)
           for sub, kind in (("op", cfg.layer_types[l]),
                             ("ff", cfg.mlp_layer_types[l])) if kind != NONE}
    n = cfg.hc_mult
    if n > 1:  # float32 all: the residual path's own coefficients
        for sub in ("op", "ff"):
            out.update({
                f"hc_{sub}_phi_pre": ((n * H, n), n * H, "hc_phi"),
                f"hc_{sub}_phi_post": ((n * H, n), n * H, "hc_phi"),
                f"hc_{sub}_phi_res": ((n * H, n * n), n * H, "hc_phi"),
                f"hc_{sub}_b_pre": ((n,), 1, "hc_bias"),
                f"hc_{sub}_b_post": ((n,), 1, "hc_bias"),
                f"hc_{sub}_b_res": ((n, n), 1, "hc_bias"),
                f"hc_{sub}_a": ((3,), 0, None)})  # a_pre, a_post, a_res
    if cfg.layer_types[l] == CONV:
        out.update(w_in=((H, 3 * H), H, None),
                   w_out=((H, H), H, "op_out"),
                   conv_w=((H, cfg.conv_L_cache), cfg.conv_L_cache, None))
    elif cfg.layer_types[l] == MAMBA:
        I, N, nh = _mamba_sizes(cfg)
        GN = cfg.mamba_n_groups * N  # a B and a C a group
        C, K = I + 2 * GN, cfg.conv_L_cache
        out.update(w_in=((H, 2 * I + 2 * GN + nh), H,   # z | xBC | dt
                         ("dt_in", 2 * I + 2 * GN)),
                   conv_w=((C, K), K, "conv_taps"),
                   conv_b=((C,), 1, "conv_bias"),
                   A_log=((nh,), 1, None), dt_bias=((nh,), 1, None),
                   D=((nh,), 0, None), ssm_norm=((I,), 0, None),
                   w_out=((I, H), I, "op_out"))
    elif cfg.layer_types[l] == LATENT:
        rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        out.update(w_qa=((H, rq), H, None), q_a_norm=((rq,), 0, None),
                   w_qb=((rq, nq * (dn + dr)), rq, None),
                   w_kva=((H, rkv + dr), H, None),
                   kv_a_norm=((rkv,), 0, None),
                   w_kvb=((rkv, nq * (dn + dv)), rkv, None),
                   wo=((nq * dv, H), nq * dv, "op_out"))
    elif cfg.layer_types[l] != NONE:
        out.update(wq=((H, nq * D), H, None), wk=((H, nkv * D), H, None),
                   wv=((H, nkv * D), H, None),
                   wo=((nq * D, H), nq * D, "op_out"))
        if not cfg.nope:
            out.update(q_norm=((D,), 0, None), k_norm=((D,), 0, None))
        if cfg.gating:
            out["wg"] = ((H, nq), H, None)
    if cfg.mlp_layer_types[l] == "dense":
        I = cfg.dense_width
        out.update(w1=((H, I), H, None), w3=((H, I), H, None),
                   w2=((I, H), I, "dense_out"))
    elif cfg.mlp_layer_types[l] == "sparse":
        E, F = cfg.n_held, cfg.moe_intermediate_size
        L = cfg.moe_latent_size or H  # what an expert's rows are wide
        gated = cfg.mlp_hidden_act != "relu2"  # a third matrix, the gate's
        out.update(gate=((H, cfg.num_experts), H, None),
                   w1=((E, L, F), L, None), w2=((E, F, L), F, "expert_out"))
        if gated:
            out["w3"] = ((E, L, F), L, None)
        if cfg.moe_latent_size:  # once a layer, around all its experts
            out.update(w_down=((H, L), H, None), w_up=((L, H), L, None))
        if cfg.use_expert_bias:
            out["gate_bias"] = ((cfg.num_experts,), 1, "gate_bias")
        S = cfg.shared_expert_intermediate_size
        if S:
            out.update(s1=((H, S), H, None), s2=((S, H), S, "shared_out"))
            if gated:
                out["s3"] = ((H, S), H, None)
    return out


@functools.partial(jax.jit, static_argnames=("shapes", "dtype"))
def _draw(key, init: dict, *, shapes: tuple, dtype: str) -> dict:
    """One layer's arrays; jitted per signature of shapes, so a stack
    compiles a few small programs and not one of every layer."""
    out = {}
    for i, (name, (shape, fan, factor)) in enumerate(shapes):
        k = jax.random.fold_in(key, i)
        if fan == 0:
            out[name] = jnp.ones(shape, jnp.float32)
            continue
        if name == "A_log":
            # ptpu: allow[unguarded-domain] — drawn from [1, 16)
            out[name] = jnp.log(jax.random.uniform(
                k, shape, jnp.float32, 1.0, 16.0))
            continue
        if name == "dt_bias":  # softplus(dt_bias) is the step drawn
            step = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(1e-3), math.log(0.1)))
            # ptpu: allow[unguarded-domain] — a step of 0.001 or more:
            # 1 - exp(-step) is positive
            out[name] = step + jnp.log(-jnp.expm1(-step))
            continue
        scale = jnp.asarray(fan ** -0.5, jnp.float32)
        if isinstance(factor, tuple):  # some columns' own factor
            scale = scale * jnp.where(jnp.arange(shape[-1]) >= factor[1],
                                      init[factor[0]], 1.0)
        elif factor is not None:
            scale = scale * init[factor]
        # drawn in the target dtype: a float32 draw of one layer's
        # experts would be 1.4 GB of scratch beside 9 GB of weights
        kind = jnp.float32 if name in ("conv_w", "conv_b", "gate_bias") \
            or name.startswith("hc_") else dtype
        out[name] = jax.random.normal(k, shape, kind) * scale.astype(kind)
        if name.endswith("_b_res"):
            out[name] += init["hc_diag"] * jnp.eye(shape[0], dtype=kind)
    return out


def init_weights(key: jax.Array, cfg: DecoderConfig,
                 init: Optional[Dict[str, float]] = None) -> dict:
    """Seeded weights on the device: matrices normal / sqrt(fan-in)
    times their factor of :data:`INIT` (``init`` overrides entries of
    it; they are traced, so another scale is not another program),
    embedding normal x ``embed`` (tied to the head, or the head drawn
    like it where ``tie_word_embeddings`` is false), gains 1, the expert
    bias normal x ``gate_bias``. ``{"embed", "norm_out", ["head",]
    "layers": [layer, ...]}``: the tree ``decoder_reference`` reads too."""
    init = {**INIT, **(init or {})}
    unit = {**init, **{k: 1.0 for k in ("op_out", "dense_out",
                                        "expert_out", "shared_out")}}
    ke, kl = jax.random.split(key)
    table = ((cfg.vocab_size, cfg.hidden_size), 1, "embed")
    top = (("embed", table), ("norm_out", ((cfg.hidden_size,), 0, None)))
    if not cfg.tie_word_embeddings:
        top += (("head", table),)
    return {
        **_draw(ke, init, shapes=top, dtype=cfg.dtype),
        "layers": [
            _draw(jax.random.fold_in(kl, l), init if l else unit,
                  shapes=tuple(sorted(_layer_shapes(cfg, l).items())),
                  dtype=cfg.dtype)
            for l in range(cfg.num_hidden_layers)]}


# -- pieces -----------------------------------------------------------------

def _rms(x, gain, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def _dot(a, w):
    return jnp.dot(a.astype(w.dtype), w,
                   preferred_element_type=jnp.float32)


#: float32 elements of one temporary that a stage of the prefill may
#: hold. A wider stage goes in equal parts, one after the other: a dense
#: feed-forward by blocks of tokens (three ``[T, intermediate]`` arrays),
#: the queries' projection, norm and rotary by groups of heads (the
#: float32 product of every head at once, and until PR 39 three more
#: arrays its size for norm and rotary: 8 GB for 64 heads at 65,536
#: slots whole, by the v5e compiler's count). Everything
#: ``lfm2-8b-a1b-l14``'s cell runs is under both, in one part.
BLOCK_ELEMENTS = 1 << 27
HEAD_GROUP_ELEMENTS = 1 << 26


def _token_blocks(fn, width: int, z):
    """``fn(z)`` over ``z [T, ...]`` in as few equal blocks of tokens as
    keep ``tokens x width`` at ``BLOCK_ELEMENTS`` or under; ``fn``
    treats every token on its own."""
    T = z.shape[0]
    n = moe.equal_parts(T, width, BLOCK_ELEMENTS)
    if n == 1:
        return fn(z)
    out = jax.lax.map(fn, z.reshape((n, T // n) + z.shape[1:]))
    return out.reshape((T,) + out.shape[2:])


#: lanes of a vector register. A head whose width is a multiple of it is
#: a whole block of an array's minor axis, so ``window_attention`` can
#: pick it out of ``heads x D`` lanes and a projection stays as the
#: product writes it; a narrower head gets an axis of its own
LANES = 128
#: tokens from which a projection's input is a STREAM (a prefill's
#: packed batch; the cells' shortest has 4,096) and under which it is a
#: decode step's rows (4 to 64)
STREAM_TOKENS = 256


def _groups(heads: int, tokens: int, *widths: int) -> int:
    """How many groups the heads of a projection of ``tokens`` tokens
    come out in: ONE (``[1, T, heads x D]``, token-major: what a matrix
    product writes) for a STREAM (``STREAM_TOKENS`` or more) whose
    every width fills whole lane tiles, else one a head
    (``[heads, T, D]``): a decode step's few rows keep the programs they
    had, a narrow head an axis of its own."""
    return 1 if tokens >= STREAM_TOKENS \
        and all(w % LANES == 0 for w in widths) else heads


def _rotary(x, pos, rope):
    """Rotate-half rotary over the first ``2 len(inv)`` dimensions of a
    head (the rest pass through): ``x [..., heads, D]``, ``pos`` shaped
    like ``x`` without its last two axes (or broadcast against them),
    ``rope`` from :meth:`DecoderConfig.rope`."""
    inv, factor = rope
    R = 2 * len(inv)
    ang = pos.astype(jnp.float32)[..., None, None] \
        * jnp.asarray(inv, jnp.float32)
    ang = jnp.concatenate([ang, ang], axis=-1)
    xr = x[..., :R]
    x1, x2 = xr[..., :R // 2], xr[..., R // 2:]
    out = xr * (jnp.cos(ang) * factor) \
        + jnp.concatenate([-x2, x1], -1) * (jnp.sin(ang) * factor)
    return out if R == x.shape[-1] \
        else jnp.concatenate([out, x[..., R:]], axis=-1)


def _project(z, w, groups):
    """``z [..., H] x w [H, W] -> [groups, ..., W / groups]`` float32,
    group ``g`` the ``g``-th block of the product's columns. One group
    is the plain product, laid out as a matrix product writes it; as
    many groups as heads is the projection written by head, whose
    output IS heads-first."""
    if groups == 1:
        return _dot(z, w)[None]
    w = w.reshape(w.shape[0], groups, -1)
    return jnp.einsum("...h,hnd->n...d", z.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def _norm_rotary(a, gain, pos, rope, D, cfg):
    """``a [G, ..., heads / G x D]`` float32 -> every head through its
    RMSNorm (``gain [D]``; ``None``: no norm) and rotary at ``pos
    [...]``, in the weights' dtype. A stream's heads side by side in the
    lanes take one pass of ``ops/head_lanes.py``; a head with an axis of
    its own (a decode step's, a narrow head's) the plain lines."""
    if a.shape[0] == 1 and a.shape[-1] > D:
        return head_lanes.head_norm_rotary(
            a[0], gain, pos, rope=rope, head_dim=D, eps=cfg.norm_eps,
            dtype=cfg.dtype)[None]
    by_head = a.reshape(a.shape[:-1] + (-1, D))
    if gain is not None:
        by_head = _rms(by_head, gain, cfg.norm_eps)
    return _rotary(by_head, pos, rope).reshape(a.shape).astype(
        jnp.dtype(cfg.dtype))


def _qkv(lw, z, pos, l, cfg):
    """``q [G, ..., heads_l / G x D]``, ``k``, ``v [Gkv, ..., kv heads /
    Gkv x D]`` of ``z [..., H]`` at positions ``pos [...]``, in the
    weights' dtype: each group's heads side by side in the lanes, the
    layout ``window_attention`` reads (:func:`_groups`; a stream's
    per-head norm and rotary by ``ops/head_lanes.py``, a head with an
    axis of its own by the plain lines). The queries go by groups
    of heads where all of them at once would hold more than
    ``HEAD_GROUP_ELEMENTS``: those groups are ``G``, nothing stacks or
    transposes them."""
    dt = jnp.dtype(cfg.dtype)
    rope = None if cfg.nope else cfg.rope(cfg.layer_types[l])
    nq, D = cfg.num_attention_heads_per_layer[l], cfg.head_dim

    def project(z, w, norm=None):
        a = _project(z, w, _groups(w.shape[1] // D, z[..., 0].size, D))
        if norm is None:
            return a.astype(dt)
        return _norm_rotary(a, norm, pos, rope, D, cfg)

    n = moe.equal_parts(nq, z[..., 0].size * D, HEAD_GROUP_ELEMENTS)
    if n == 1:
        q = project(z, lw["wq"], lw.get("q_norm"))
    else:
        zb = z.astype(lw["wq"].dtype)  # read once a group: half the bytes

        def part(w):  # [T, lanes] where the part's heads are one group
            a = project(zb, w, lw.get("q_norm"))
            return a[0] if a.shape[0] == 1 else a

        q = jax.lax.map(
            part, lw["wq"].reshape(-1, n, nq // n * D).swapaxes(0, 1))
        q = q.reshape((-1,) + q.shape[-2:])
    return q, project(z, lw["wk"], lw.get("k_norm")), project(z, lw["wv"])


def _attention_out(lw, o, z, D):
    """``o [G, ..., heads / G x D]`` through the head gate (``gating``:
    each head times ``sigmoid(z W_g)``, one scalar a head) and ``W_o``,
    one product over ``heads x D`` (a sum of ``G``). A stream's heads in
    the lanes take the gate in one pass of ``ops/head_lanes.py``; heads
    with an axis of their own (a decode step's, a narrow head's) take a
    broadcast along it."""
    G = o.shape[0]
    if "wg" in lw:  # a float32 product, rounded once for the next one
        gate = jax.nn.sigmoid(_dot(z, lw["wg"]))
        gate = jnp.moveaxis(gate.reshape(gate.shape[:-1] + (G, -1)), -2, 0)
        if o.shape[-1] > D:  # a stream's heads side by side
            o = head_lanes.head_gate(
                o.reshape(-1, o.shape[-1]), gate.reshape(-1, gate.shape[-1]),
                head_dim=D).reshape(o.shape)
        else:
            o = (o.astype(jnp.float32).reshape(o.shape[:-1] + (-1, D))
                 * gate[..., None]).reshape(o.shape)
    if G == 1:
        return _dot(o[0], lw["wo"])
    wo = lw["wo"].reshape(G, o.shape[-1], -1)
    return jnp.einsum("n...d,ndh->...h", o.astype(wo.dtype), wo,
                      preferred_element_type=jnp.float32)


def _mamba_sizes(cfg) -> Tuple[int, int, int]:
    """``(inner width, state size, heads)`` of a ``mamba`` layer."""
    return (cfg.mamba_n_heads * cfg.mamba_d_head, cfg.mamba_d_state,
            cfg.mamba_n_heads)


def _swiglu(z, w1, w3, w2):
    return _dot(jax.nn.silu(_dot(z, w1)) * _dot(z, w3), w2)


def _relu2(z, w1, w2):
    """A feed-forward of two matrices: ``relu(z W_1)^2 W_2``."""
    return _dot(jnp.square(jax.nn.relu(_dot(z, w1))), w2)


def _feed_forward(lw, z, valid, cfg):
    """Dense or expert feed-forward of ``z [T, H]``; ``(out, load)``
    with ``load [E]`` (``None`` for a dense layer). The shared expert,
    where the family has one, is a dense feed-forward that every token
    takes at weight 1: added once, here, whatever share of the routed
    experts this chip holds. Experts in a latent (``w_down``, ``w_up``:
    ``moe_latent_size``) take ``z W_down`` for their rows, and the sum
    of what the held ones give goes through ``W_up`` ONCE; the router
    and the shared expert read ``z`` itself."""
    if "gate" not in lw:
        return _token_blocks(
            lambda z: _swiglu(z, lw["w1"], lw["w3"], lw["w2"]),
            cfg.dense_width, z), None
    sel, wts = moe.route(z, lw["gate"], lw.get("gate_bias"),
                         top_k=cfg.num_experts_per_tok,
                         norm_topk=cfg.norm_topk_prob,
                         scale=cfg.routed_scaling_factor)
    rows = _dot(z, lw["w_down"]) if "w_down" in lw else z
    out = moe.expert_product(
        rows.astype(jnp.dtype(cfg.dtype)), sel, wts, lw["w1"], lw.get("w3"),
        lw["w2"], n_experts=cfg.num_experts, held=cfg.experts_held,
        valid=valid)
    if "w_up" in lw:
        out = _dot(out, lw["w_up"])
    if "s3" in lw:
        out = out + _swiglu(z, lw["s1"], lw["s3"], lw["s2"])
    elif "s1" in lw:
        out = out + _relu2(z, lw["s1"], lw["s2"])
    return out, moe.expert_load(sel, cfg.num_experts, valid)


# -- the residual path --------------------------------------------------------

def _hc_coefficients(lw, sub, x, cfg):
    """What sub-block ``sub`` (``op`` or ``ff``) of a layer reads, writes
    and mixes, from the ``n = hc_mult`` streams ``x [n, T, H]`` float32
    themselves: ``(pre [n, T], post [n, T], res [n, n, T])`` with ``res[i,
    j]`` what stream ``i`` takes of stream ``j``. ``x~ = vec(x) / sqrt(
    mean(vec(x)^2) + hc_eps)`` over all ``n H`` of a token; ``pre =
    sigmoid(a_pre x~ phi_pre + b_pre)``, ``post = 2 sigmoid(..)``, ``res``
    the Sinkhorn normalisation of ``exp(clip(a_res x~ phi_res + b_res))``:
    ``hc_sinkhorn_iters`` times its rows, then its columns, over their
    sums + ``hc_eps``. Float32 throughout (the projection at ``highest``:
    24 columns decide how four streams of ``H`` are mixed); the tokens
    lie on the minor axis, so that twenty passes over ``[n, n]`` a token
    are passes over a few whole vectors."""
    n, _, H = x.shape
    f32, eps = jnp.float32, cfg.hc_eps
    phi = jnp.concatenate([lw[f"hc_{sub}_phi_{k}"]
                           for k in ("pre", "post", "res")], axis=1)
    # a stream at a time: vec(x) is never laid out
    p = sum(jnp.einsum("th,hc->ct", x[j], phi[j * H:(j + 1) * H],
                       precision="highest", preferred_element_type=f32)
            for j in range(n))
    p = p * jax.lax.rsqrt(jnp.mean(x * x, axis=(0, 2)) + eps)
    a = lw[f"hc_{sub}_a"]
    pre = jax.nn.sigmoid(a[0] * p[:n] + lw[f"hc_{sub}_b_pre"][:, None])
    post = 2.0 * jax.nn.sigmoid(a[1] * p[n:2 * n]
                                + lw[f"hc_{sub}_b_post"][:, None])
    res = jnp.exp(jnp.clip(
        a[2] * p[2 * n:].reshape(n, n, -1)
        + lw[f"hc_{sub}_b_res"][:, :, None],
        cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max))
    for _ in range(cfg.hc_sinkhorn_iters):
        res = res / (jnp.sum(res, axis=1, keepdims=True) + eps)
        res = res / (jnp.sum(res, axis=0, keepdims=True) + eps)
    return pre, post, res


def _hc_columns(lw, sub, n):
    """The weights of :func:`_hc_coefficients` a column of the projection
    at a time, as ``ops/hyper_mix.py`` takes them: ``(phi [n H, c], a
    [c], b [c])``, the columns ``pre``, ``post``, then ``res`` row by
    row."""
    a = lw[f"hc_{sub}_a"]
    return (jnp.concatenate([lw[f"hc_{sub}_phi_{k}"]
                             for k in ("pre", "post", "res")], axis=1),
            a[np.repeat(np.arange(3), (n, n, n * n))],
            jnp.concatenate([lw[f"hc_{sub}_b_{k}"].reshape(-1)
                             for k in ("pre", "post", "res")]))


def _sub_block(lw, sub, x, fn, cfg, valid=None):
    """One sub-block around the residual path: ``(x', what fn returns
    beside its output, gap)``. ``fn`` takes the normalised input ``[T,
    H]`` and returns ``(out [T, H], aux)``. One stream (``hc_mult`` 1; a
    branch taken when the program is traced): ``x' = x + fn(n(x))`` over
    ``x [T, H]`` and no gap. ``n`` streams ``x [n, T, H]``: ``u = pre .
    x``, ``x'_i = sum_j res[i, j] x_j + post_i fn(n(u))``, and ``gap``
    the largest ``|sum_j res[i, j] - 1|`` over the ``valid`` tokens (all
    where ``None``): what the Sinkhorn iterations left. A stream of a
    tile's tokens or more (a prefill; told when the program is traced)
    goes through the two kernels of ``ops/hyper_mix.py``, which read the
    streams twice and write them once; a decode step's few tokens are
    written as sums of ``n`` scaled streams (elementwise: sixty-odd
    operations of under a microsecond, 0.025 ms a sub-block)."""
    norm = lw[sub + "_norm"]
    if cfg.hc_mult == 1:
        out, aux = fn(_rms(x, norm, cfg.norm_eps))
        if cfg.residual_multiplier != 1:
            out = cfg.residual_multiplier * out
        return x + out, aux, None
    n = cfg.hc_mult
    if x.shape[1] >= hyper_mix.TILE:
        z, coef = hyper_mix.hyper_mix_read(
            x, *_hc_columns(lw, sub, n), norm, eps=cfg.hc_eps,
            norm_eps=cfg.norm_eps, iters=cfg.hc_sinkhorn_iters,
            clamp=(cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max))
        out, aux = fn(z)
        new = hyper_mix.hyper_mix_write(x, out, coef)
        res = hyper_mix.coefficients(coef, n)[2]
    else:
        pre, post, res = _hc_coefficients(lw, sub, x, cfg)
        u = sum(pre[j][:, None] * x[j] for j in range(n))
        out, aux = fn(_rms(u, norm, cfg.norm_eps))
        new = jnp.stack([
            sum(res[i, j][:, None] * x[j] for j in range(n))
            + post[i][:, None] * out for i in range(n)])
    off = jnp.abs(jnp.sum(res, axis=1) - 1.0)
    if valid is not None:
        off = jnp.where(valid[None, :], off, 0.0)
    return new, aux, jnp.max(off)


def _streams_in(x, cfg):
    """The embedding ``[T, H]`` as the residual path takes it: itself, or
    ``hc_mult`` copies of it."""
    return x if cfg.hc_mult == 1 \
        else jnp.broadcast_to(x, (cfg.hc_mult,) + x.shape)


def _streams_out(x, cfg):
    """What the head reads: the stream, or the sum of the ``n``."""
    return x if cfg.hc_mult == 1 else jnp.sum(x, axis=0)


def _stream_rows(x, at, cfg):
    """Slots ``at [B]`` of the stream or of each of the ``n``. The ``n``
    as one ``[n T, H]`` array of rows: a gather along the middle axis of
    ``[n, T, H]`` wants the streams in another layout than the kernel
    that wrote them leaves, a copy of all of them for ``B`` rows."""
    if cfg.hc_mult == 1:
        return x[..., at, :]
    n, T, H = x.shape
    return x.reshape(n * T, H)[jnp.arange(n)[:, None] * T + at]


# -- latent attention ---------------------------------------------------------

def _latent_project(lw, z, pos, cfg):
    """``(cq [..., q_lora_rank] float32, kv [..., kv_lora_rank + rope])``
    of ``z [..., H]`` at positions ``pos [...]``. ``cq``: the low-rank
    query path, normalised, before its up-projection ``W_qb`` (each
    head's last ``rope`` columns are the rotated ones). ``kv``: what a
    token leaves in the cache, its normalised latent beside its rotated
    key, ONE for all heads, in the weights' dtype."""
    rope = cfg.rope(LATENT)
    rkv = cfg.kv_lora_rank
    cq = _rms(_dot(z, lw["w_qa"]), lw["q_a_norm"], cfg.norm_eps)
    kv = _dot(z, lw["w_kva"])
    c = _rms(kv[..., :rkv], lw["kv_a_norm"], cfg.norm_eps)
    r = _rotary(kv[..., None, rkv:], pos, rope)[..., 0, :]
    return cq, jnp.concatenate([c, r], axis=-1).astype(jnp.dtype(cfg.dtype))


def _latent_up(lw, cfg):
    """``W_kvb [kv_lora_rank, heads x (nope + v)]`` as ``(W_uk, W_uv)``,
    each ``[kv_lora_rank, heads, nope or v]``."""
    w = lw["w_kvb"].reshape(cfg.kv_lora_rank, cfg.num_attention_heads, -1)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


# -- prefill ----------------------------------------------------------------

def _conv_prefill(lw, z, valid, pos, last, cfg):
    """``z [T, H]`` packed. A tap ``s`` slots back is the slot ``s``
    before in the stream where the token is at least ``s`` into its row,
    and zero where it is not. ``last [B]``: each row's last slot, for
    the window the decode goes on from."""
    b, c, u = jnp.split(_dot(z, lw["w_in"]), 3, axis=-1)
    v = jnp.where(valid[:, None], b * u, 0.0)
    y = _causal_taps(v, lw["conv_w"], pos)
    win = _row_tail(v, last, pos, cfg.conv_L_cache)
    return _dot(c * y, lw["w_out"]), {"win": win}


def _causal_taps(v, w, pos):
    """The depthwise causal conv of a packed stream ``v [T, C]`` with
    taps ``w [C, K]`` (the last one the token's own): a tap ``s`` slots
    back adds nothing where the token is fewer than ``s`` into its
    row."""
    T, K = v.shape[0], w.shape[1]
    y = w[:, K - 1] * v
    for s in range(1, K):
        back = jnp.pad(v, ((s, 0), (0, 0)))[:T]
        y = y + w[:, K - 1 - s] * jnp.where((pos >= s)[:, None], back, 0.0)
    return y


def _row_tail(v, last, pos, n: int):
    """``[B, n, C]``: the ``n`` last slots of each row of the stream ``v
    [T, C]``, oldest first, zeros where a row is shorter."""
    ago = jnp.arange(n - 1, -1, -1, dtype=jnp.int32)
    # ptpu: allow[materialized-gather] — the last slots of each row, the
    # state itself
    win = jnp.take(v, jnp.maximum(last[:, None] - ago, 0), axis=0)
    return jnp.where((pos[last][:, None] >= ago)[..., None], win, 0.0)


def _mamba_inputs(lw, z, cfg):
    """``(gate [.., I], raw xBC [.., I + 2 G N], dt [.., heads])``
    float32 of ``z [.., H]``: the in-projection's three parts (``xBC``:
    ``x``, then a ``B`` a group, then a ``C`` a group)."""
    I, N, _ = _mamba_sizes(cfg)
    C = I + 2 * cfg.mamba_n_groups * N
    zxd = _dot(z, lw["w_in"])
    return zxd[..., :I], zxd[..., I:I + C], zxd[..., I + C:]


def _mamba_out(lw, y, x, gate, cfg):
    """From the scan's ``y`` to the layer's output: the skip ``D x`` a
    head, the gate ``silu(z)``, the RMSNorm of each of the
    ``mamba_n_groups`` groups of inner channels over ITS OWN mean square
    (one group: over all of them) and ``W_out``; float32 up to the
    product."""
    y = y + jnp.repeat(lw["D"], cfg.mamba_d_head) * x
    g, G = y * jax.nn.silu(gate), cfg.mamba_n_groups
    if G == 1:  # the lines (and the compiled program) one group always had
        return _dot(_rms(g, lw["ssm_norm"], cfg.norm_eps), lw["w_out"])
    by_group = _rms(g.reshape(g.shape[:-1] + (G, -1)), 1.0, cfg.norm_eps)
    return _dot(by_group.reshape(g.shape) * lw["ssm_norm"], lw["w_out"])


def _mamba_prefill(lw, z, valid, pos, rows, cfg):
    """``z [T, H]`` packed. The depthwise conv over ``xBC`` as
    :func:`_conv_prefill` has its taps (a tap that would reach before a
    row's first token adds zero), with a bias and a ``silu``; then the
    recurrence over the stream (``ops/ssm_scan.py``: chunks of
    ``mamba_chunk_size``, restarted at each row's first token by the
    rows' ids), whose inputs are zeroed in the spare slots. The state:
    each row's ``S`` after its last token, ``[B, N, heads x head_dim]``
    float32, beside its last ``conv_L_cache - 1`` raw ``xBC``."""
    I, N, _ = _mamba_sizes(cfg)
    GN = cfg.mamba_n_groups * N
    dt_ = jnp.dtype(cfg.dtype)
    gate, raw, dt = _mamba_inputs(lw, z, cfg)
    y = lw["conv_b"] + _causal_taps(raw, lw["conv_w"], pos)
    xbc = jnp.where(valid[:, None], jax.nn.silu(y), 0.0)
    dt = jnp.where(valid[:, None], jax.nn.softplus(dt + lw["dt_bias"]), 0.0)
    x = xbc[:, :I]
    y, final = ssm_scan.ssm_scan(
        x.astype(dt_), xbc[:, I:I + GN].astype(dt_),
        xbc[:, I + GN:].astype(dt_), dt, -jnp.exp(lw["A_log"]), rows.row,
        rows.last, chunk=cfg.mamba_chunk_size, groups=cfg.mamba_n_groups)
    return _mamba_out(lw, y, x, gate, cfg), {
        "ssm": final,
        "win": _row_tail(raw, rows.last, pos, cfg.conv_L_cache - 1)}


def _gather(a, at):
    """Entries ``at [...]`` of the second axis of every group of ``a [G,
    N, ...]``: ``[G, ..., ...]`` (all in bounds, by their makers). The
    groups as ONE ``[G N, ...]`` array: a gather along a middle axis
    wants the groups laid inside the tokens, a copy of its operand
    before it and of its result after (seen compiling for the v5e)."""
    G, N = a.shape[:2]
    at = (jnp.arange(G, dtype=at.dtype).reshape((G,) + (1,) * at.ndim) * N
          + at)
    # ptpu: allow[materialized-gather] — the rows (or the stream) itself
    return a.reshape((G * N,) + a.shape[2:]).at[at].get(
        mode="promise_in_bounds")


def _to_rows(a, rows):
    """The stream ``a [G, T, W]`` as right-aligned rows ``[G, B, history,
    W]``, WHOLE TILES of ``align`` slots at a time (``src [B, history /
    align]``: the tile of the stream each tile of a row is). A row's end
    is a multiple of ``align`` in the stream (:func:`row_ends`) and in
    its ``history`` slots, so a row's tiles ARE the stream's: nothing
    moves inside a tile, where a slot-by-slot gather moves every row of
    every tile on its own (3.1 ms against 9.4 for a sliding layer's
    queries at 32,768 slots, PR 38's builder's chip run). What a row's
    slots before its first hold is its neighbour's tokens or spare
    slots: finite, which is all the kernel asks; the state zeroes
    them."""
    G, T, W = a.shape
    out = _gather(a.reshape(G, T // rows.align, rows.align, W), rows.src)
    return out.reshape(G, rows.src.shape[0], -1, W)


def _to_stream(o, rows):
    """The rows' ``o [G, B, history, W]`` back into the stream ``[G, T,
    W]``, whole tiles again (``dst [T / align]``: the tile of the rows
    each tile of the stream is; a spare slot takes what lies beside)."""
    G, W = o.shape[0], o.shape[-1]
    out = _gather(o.reshape(G, -1, rows.align, W), rows.dst)
    return out.reshape(G, -1, W)


def _cache_layout(a, D):
    """``a [G, B, slots, heads / G x D]`` as the decode reads its state:
    batch first, a head an axis, ``[B, heads, slots, D]``. The one
    transpose between a prefill's projections and the cache, of the few
    key-value heads: each head's lanes are sliced out whole and laid
    behind the batch, nothing is reshaped across a tile."""
    W = a.shape[-1]
    if W == D:  # a head a group already: only the batch comes first
        return a.swapaxes(0, 1)
    return jnp.stack([g[..., h:h + D] for g in a for h in range(0, W, D)],
                     axis=1)


def _attention_prefill(lw, z, pos, rows, room, l, cfg):
    """``z [T, H]`` packed. ``q``, ``k``, ``v`` are gathered into the
    right-aligned rows ``[G, B, history, lanes]`` (``rows``: where each
    of its slots lies in the stream, which of them are real, where each
    slot of the stream lies in it, and each row's first real slot) in
    the layout their projections have, attention is taken blockwise
    from each row's first real slot, and the output is gathered back
    into the stream in that layout too. The state: a full layer's keys
    and values ``[B, kv heads, history, D]`` with ``room`` behind them;
    a sliding layer's ring, slot ``s`` holding the row's last token
    whose position is ``s`` modulo the window (zeros where it has
    none)."""
    real, lead, last = rows.real, rows.lead, rows.last
    D = cfg.head_dim
    sliding = cfg.layer_types[l] == SLIDING
    q, k, v = _qkv(lw, z, pos, l, cfg)
    kr, vr = _to_rows(k, rows), _to_rows(v, rows)
    o = window_attention(
        _to_rows(q, rows), kr, vr, lead, scale=cfg.attention_scale,
        window=cfg.sliding_window if sliding else None,
        block=ATTENTION_BLOCK, head_dim=D)
    out = _attention_out(lw, _to_stream(o, rows), z, D)

    def state(a, ok, room=0):  # zeros where a row has no token
        return jnp.pad(jnp.where(ok[:, None, :, None], _cache_layout(a, D),
                                 0), ((0, 0), (0, 0), (0, room), (0, 0)))

    if not sliding:
        return out, {"k": state(kr, real, room), "v": state(vr, real, room)}
    W = cfg.sliding_window
    at = jnp.arange(W, dtype=jnp.int32)[None, :]
    back = (pos[last][:, None] - at) % W  # tokens back from a row's last
    ring, held = last[:, None] - back, back <= pos[last][:, None]
    ring = jnp.where(held, ring, 0)
    return out, {"k": state(_gather(k, ring), held),
                 "v": state(_gather(v, ring), held)}


def _latent_prefill(lw, z, pos, rows, room, cfg):
    """``z [T, H]`` packed, in the EXPANDED form (the tokens are in
    hand): every head's own keys ``k_nope`` and values from the tokens'
    latents through ``W_kvb``, gathered into the right-aligned rows
    like any attention layer's, through the blockwise kernel at the
    family's scale with the score as TWO products: each head's
    ``q_nope . k_nope`` plus its ``q_rope`` against the rotated key,
    which is ONE for all heads and is never repeated (where the heads
    lie in the lanes, both rotated halves are padded to whole lane
    tiles with zeros, which add nothing). The state is what the decode
    attends over instead: the latents themselves, ``[B, history + room,
    kv_lora_rank + rope]``."""
    real, lead = rows.real, rows.lead
    dt = jnp.dtype(cfg.dtype)
    n, rkv = cfg.num_attention_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    G = _groups(n, z.shape[0], dn, dv)
    pad = -dr % LANES if G == 1 else 0
    cq, kv = _latent_project(lw, z, pos, cfg)
    w_q = lw["w_qb"].reshape(-1, n, dn + dr)
    q = _project(cq, w_q[..., :dn].reshape(-1, n * dn), G).astype(dt)
    q_r = _project(cq, jnp.pad(w_q[..., dn:], ((0, 0), (0, 0), (0, pad)))
                   .reshape(-1, n * (dr + pad)), G)
    q_r = _norm_rotary(q_r, None, pos, cfg.rope(LATENT), dr + pad, cfg)
    w_uk, w_uv = _latent_up(lw, cfg)
    c = kv[:, :rkv]
    k = _project(c, w_uk.reshape(rkv, -1), G).astype(dt)
    v = _project(c, w_uv.reshape(rkv, -1), G).astype(dt)
    cache = _to_rows(kv[None], rows)
    k_r = jnp.pad(cache[..., rkv:], ((0, 0),) * 3 + ((0, pad),))
    o = window_attention(
        _to_rows(q, rows), _to_rows(k, rows), _to_rows(v, rows), lead,
        _to_rows(q_r, rows), k_r, scale=cfg.latent_scale,
        block=ATTENTION_BLOCK, head_dim=dn)
    # the state: zeros where a row has no token
    return _attention_out(lw, _to_stream(o, rows), z, dv), \
        {"kv": jnp.pad(jnp.where(real[..., None], cache[0], 0),
                       ((0, 0), (0, room), (0, 0)))}


def _embed(w, tokens, cfg):
    """The residual stream's entry: a token's row of the embedding,
    float32, times ``embedding_multiplier``."""
    # ptpu: allow[materialized-gather] — the embedding lookup itself: the
    # [T, H] it makes is the residual stream
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    return x if cfg.embedding_multiplier == 1 \
        else x * cfg.embedding_multiplier


def _stack(loads, cfg):
    """The expert layers' loads ``[expert layers, E]`` (none: ``[0,
    E]``)."""
    return jnp.stack(loads) if loads \
        else jnp.zeros((0, cfg.num_experts), jnp.int32)


def _head(w, x, cfg):
    z = _rms(x, w["norm_out"], cfg.norm_eps)
    table = w["embed"] if cfg.tie_word_embeddings else w["head"]
    logits = jnp.dot(z.astype(table.dtype), table.T,
                     preferred_element_type=jnp.float32)
    return logits if cfg.logits_scaling == 1 \
        else logits / cfg.logits_scaling


def row_align(history: int, dtype) -> int:
    """Slots a tile: how many slots of the stream move as one into the
    right-aligned rows and back. The rows of one memory tile of the
    weights' dtype (8 of float32, 16 of bfloat16), held to a divisor of
    ``history``, so that a row that ends on a tile's edge in the stream
    ends on one in its ``history`` slots."""
    return math.gcd(history, 32 // jnp.dtype(dtype).itemsize)


def row_ends(lengths, align: int):
    """Where each row of a packed stream ENDS (one past its last token):
    every row takes its length rounded up to whole tiles of ``align``
    slots, its tokens at the END of them. The engine that packs a
    stream (numpy) and the program that reads it (traced) both ask
    here; ``row_ends(lengths, align)[-1]`` slots hold the batch."""
    xp = jnp if isinstance(lengths, jax.Array) else np
    return xp.cumsum(-(-lengths // align) * align)


class _Rows(NamedTuple):
    """What the attention layers lay a packed stream out as
    right-aligned ``[B, history]`` rows by, whole tiles of ``align``
    slots at a time."""
    src: jax.Array   #: [B, history / align]: the stream's tile a row's is
    real: jax.Array  #: [B, history]: the row's slot holds a token
    dst: jax.Array   #: [T / align]: the rows' tile the stream's is
    lead: jax.Array  #: [B]: each row's first real slot
    last: jax.Array  #: [B]: each row's last slot in the stream
    row: jax.Array   #: [T]: the row a slot of the stream belongs to
    align: int       #: slots a tile (:func:`row_align`)


def _row_maps(lengths, T: int, history: int, dtype):
    """What a packed stream of ``T`` slots is, from its rows' ``lengths
    [B]``: ``(valid [T]``: a slot holds a row's token, ``pos [T]``: its
    position in its row, ``rows)``, the last a :class:`_Rows`."""
    B = lengths.shape[0]
    align = row_align(history, dtype)
    if T % align:
        raise ValueError(f"a stream of {T} slots is not whole tiles of "
                         f"{align}")
    lengths = lengths.astype(jnp.int32)
    ends = row_ends(lengths, align)
    first = ends - lengths
    slot = jnp.arange(T, dtype=jnp.int32)
    row = jnp.minimum(jnp.searchsorted(ends, slot, side="right",
                                       method="compare_all"), B - 1)
    pos = slot - first[row]
    valid = (pos >= 0) & (slot < ends[-1])
    # slot ``a`` of row ``r`` is slot ``ends[r] - history + a`` of the
    # stream, so the row's tile ``j`` is the stream's tile ``(ends[r] -
    # history) / align + j`` (0 where that lies before the stream: a
    # tile none of the row's tokens is in), and a tile of the stream
    # lies in the row of its last slot
    at = jnp.arange(history, dtype=jnp.int32)[None, :]
    lead = history - lengths
    tile = jnp.arange(history // align, dtype=jnp.int32)[None, :]
    in_rows = (row * history + lead[row] + pos)[align - 1::align] // align
    return valid, pos, _Rows(
        src=jnp.maximum((ends[:, None] - history) // align + tile, 0),
        real=at >= lead[:, None],
        dst=jnp.where(valid[align - 1::align], in_rows, 0), lead=lead,
        last=ends - 1, row=row, align=align)


@functools.partial(jax.jit, static_argnames=("cfg", "history", "room"))
def _gen_prefill(w: dict, tokens: jax.Array, lengths: jax.Array, *,
                 cfg: DecoderConfig, history: int, room: int):
    """``tokens [T]``: the rows' tokens one row behind the other, row
    ``r``'s ENDING at slot ``row_ends(lengths, align)[r]`` (any id in
    the spare slots: the few before a row's first token, those behind
    the last row); ``lengths [B]``, each from 1 to ``history``; ``T`` a
    multiple of ``align = row_align(history, cfg.dtype)`` that holds
    them -> ``(last_logits [B, V] float32, state)``, the state laid out
    at ``history`` slots and room for ``room`` more tokens."""
    lengths = lengths.astype(jnp.int32)
    valid, pos, rows = _row_maps(lengths, tokens.shape[0], history, cfg.dtype)
    x = _streams_in(_embed(w, tokens, cfg), cfg)
    states, loads, gaps = [], [], []
    for l, (lw, kind) in enumerate(zip(w["layers"], cfg.layer_types)):
        def op(z, lw=lw, kind=kind, l=l):
            if kind == CONV:
                return _conv_prefill(lw, z, valid, pos, rows.last, cfg)
            if kind == MAMBA:
                return _mamba_prefill(lw, z, valid, pos, rows, cfg)
            if kind == LATENT:
                return _latent_prefill(lw, z, pos, rows, room, cfg)
            return _attention_prefill(lw, z, pos, rows, room, l, cfg)

        st, load, gap_op, gap_ff = {}, None, None, None
        if kind != NONE:
            x, st, gap_op = _sub_block(lw, "op", x, op, cfg, valid)
        if cfg.mlp_layer_types[l] != NONE:
            x, load, gap_ff = _sub_block(
                lw, "ff", x,
                lambda z, lw=lw: _feed_forward(lw, z, valid, cfg), cfg,
                valid)
        states.append(st)
        if load is not None:
            loads.append(load)
        gaps += [g for g in (gap_op, gap_ff) if g is not None]
    cache = jnp.arange(history + room, dtype=jnp.int32)[None, :]
    state = {"layers": states, "load": _stack(loads, cfg), "pos": lengths,
             "valid": (cache >= rows.lead[:, None]) & (cache < history),
             "filled": jnp.asarray(history, jnp.int32)}
    if gaps:  # hc_mult over 1: what twenty Sinkhorn passes left
        state["sinkhorn_gap"] = jnp.max(jnp.stack(gaps))
    last = _stream_rows(x, rows.last, cfg)
    return _head(w, _streams_out(last, cfg), cfg), state


# -- decode -----------------------------------------------------------------

def _conv_step(lw, z, st, cfg):
    b, c, u = jnp.split(_dot(z, lw["w_in"]), 3, axis=-1)
    win = jnp.concatenate([st["win"][:, 1:], (b * u)[:, None]], axis=1)
    # elementwise, as the prefill has it: a float32 einsum would go
    # through the MXU at one bfloat16 pass
    y = sum(lw["conv_w"][:, j] * win[:, j] for j in range(win.shape[1]))
    return _dot(c * y, lw["w_out"]), {"win": win}


def _mamba_step(lw, z, st, cfg):
    """One token a row: the conv over the window and the new ``xBC``,
    then ``S' = exp(dt A) S + dt x B^T`` and ``y = S' C``
    (``ops/ssm_scan.py::ssm_step``: the state goes through once, in its
    own buffer), float32 throughout."""
    I, N, _ = _mamba_sizes(cfg)
    GN = cfg.mamba_n_groups * N
    gate, raw, dt = _mamba_inputs(lw, z, cfg)
    win = st["win"]
    y = lw["conv_b"] + lw["conv_w"][:, -1] * raw + sum(
        lw["conv_w"][:, j] * win[:, j] for j in range(win.shape[1]))
    xbc = jax.nn.silu(y)
    dt = jax.nn.softplus(dt + lw["dt_bias"])
    x = xbc[:, :I]
    new, y = ssm_scan.ssm_step(
        st["ssm"], x, xbc[:, I:I + GN], xbc[:, I + GN:],
        jnp.exp(-dt * jnp.exp(lw["A_log"])), dt)
    return _mamba_out(lw, y, x, gate, cfg), {
        "ssm": new,
        "win": jnp.concatenate([win[:, 1:], raw[:, None]], axis=1)}


def _attention_step(lw, z, st, valid, pos, at, l, cfg):
    """One query a row against its state. A full layer's new key and
    value land in slot ``at`` of every row, which ``valid`` already
    counts; a sliding layer's in slot ``pos mod window`` of its ring,
    over the oldest, and the ring's slots up to ``pos`` are the real
    ones until it has wrapped."""
    D = cfg.head_dim
    q, k, v = (a.swapaxes(0, 1).reshape(a.shape[1], -1, D)
               for a in _qkv(lw, z, pos, l, cfg))
    if cfg.layer_types[l] == SLIDING:
        W = st["k"].shape[2]

        def put(ring, new):
            return jax.vmap(
                lambda r, n, s: jax.lax.dynamic_update_slice_in_dim(
                    r, n[:, None], s, 1))(ring, new, pos % W)

        ks, vs = put(st["k"], k), put(st["v"], v)
        valid = jnp.arange(W, dtype=jnp.int32)[None, :] <= pos[:, None]
    else:
        ks = jax.lax.dynamic_update_slice_in_dim(st["k"], k[:, :, None], at, 2)
        vs = jax.lax.dynamic_update_slice_in_dim(st["v"], v[:, :, None], at, 2)
    B, nkv, _ = k.shape
    s = jnp.einsum("bgrd,bgsd->bgrs", q.reshape(B, nkv, -1, D), ks,
                   preferred_element_type=jnp.float32) * cfg.attention_scale
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    o = jnp.einsum("bgrs,bgsd->bgrd",
                   jax.nn.softmax(s, axis=-1).astype(vs.dtype), vs,
                   preferred_element_type=jnp.float32)
    return _attention_out(lw, o.reshape(B, -1, D).swapaxes(0, 1), z, D), \
        {"k": ks, "v": vs}


def _latent_step(lw, z, st, valid, pos, at, cfg):
    """One query a row against the latents, the up-projections ABSORBED:
    ``q_lat = q_nope W_uk^T`` a head, scores ``[q_lat | q_rope] . [c_kv |
    k_rope]`` over the cache as it lies (one product 576 wide),
    ``o_lat = p c_kv``, ``o = o_lat W_uv``. No key or value of any head
    is ever laid out over the cache."""
    dt = jnp.dtype(cfg.dtype)
    dn, rkv = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    cq, kv = _latent_project(lw, z, pos, cfg)   # [B, 768], [B, 576]
    q = _project(cq, lw["w_qb"], cfg.num_attention_heads)  # [heads, B, 192]
    q_rope = _rotary(q[..., None, dn:], pos, cfg.rope(LATENT))[..., 0, :]
    cache = jax.lax.dynamic_update_slice_in_dim(st["kv"], kv[:, None], at, 1)
    w_uk, w_uv = _latent_up(lw, cfg)
    q_lat = jnp.einsum("nbd,cnd->bnc", q[..., :dn].astype(dt), w_uk,
                       preferred_element_type=jnp.float32).astype(dt)
    qs = jnp.concatenate([q_lat, q_rope.astype(dt).swapaxes(0, 1)], axis=-1)
    s = jnp.einsum("bnc,bsc->bns", qs, cache,
                   preferred_element_type=jnp.float32) * cfg.latent_scale
    s = jnp.where(valid[:, None, :], s, -jnp.inf)
    # over the cache's whole width, the rotated key's columns dropped
    # after: a slice of the cache itself would be a copy of it a step
    o_lat = jnp.einsum("bns,bsc->bnc",
                       jax.nn.softmax(s, axis=-1).astype(dt), cache,
                       preferred_element_type=jnp.float32)[..., :rkv]
    o = jnp.einsum("bnc,cnd->nbd", o_lat.astype(dt), w_uv,
                   preferred_element_type=jnp.float32)
    return _attention_out(lw, o, z, cfg.v_head_dim), {"kv": cache}


def _layer_step(lw, l, x, st, valid, pos, at, cfg):
    def op(z):
        if cfg.layer_types[l] == CONV:
            return _conv_step(lw, z, st, cfg)
        if cfg.layer_types[l] == MAMBA:
            return _mamba_step(lw, z, st, cfg)
        if cfg.layer_types[l] == LATENT:
            return _latent_step(lw, z, st, valid, pos, at, cfg)
        return _attention_step(lw, z, st, valid, pos, at, l, cfg)

    load = None
    if cfg.layer_types[l] != NONE:
        x, st, _ = _sub_block(lw, "op", x, op, cfg)
    if cfg.mlp_layer_types[l] != NONE:
        x, load, _ = _sub_block(
            lw, "ff", x, lambda z: _feed_forward(lw, z, None, cfg), cfg)
    return x, st, load


def _decode_step(w, state, tok, cfg):
    """Append ``tok [B]`` to every row: ``(logits [B, V], state, load
    [expert layers, E])``."""
    at, pos = state["filled"], state["pos"]
    valid = jax.lax.dynamic_update_slice_in_dim(
        state["valid"], jnp.ones((tok.shape[0], 1), bool), at, 1)
    x = _streams_in(_embed(w, tok, cfg), cfg)
    states, loads = [], []
    for l, (lw, st) in enumerate(zip(w["layers"], state["layers"])):
        x, st, load = _layer_step(lw, l, x, st, valid, pos, at, cfg)
        states.append(st)
        if load is not None:
            loads.append(load)
    new = {**state, "layers": states, "valid": valid, "filled": at + 1,
           "pos": pos + 1}
    return _head(w, _streams_out(x, cfg), cfg), new, _stack(loads, cfg)


@functools.partial(jax.jit, static_argnames=("cfg", "steps"),
                   donate_argnames=("state",))
def _gen_decode(w: dict, state: dict, first: jax.Array, *,
                cfg: DecoderConfig, steps: int):
    """Greedy generation of ``steps`` tokens a row from the prefill's
    ``state`` (donated) and its last logits ``first [B, V]``: token 0 is
    their argmax, then ``steps - 1`` forward passes. Returns ``(tokens
    [B, steps] int32, scores [B, steps] float32, expert_load, state)``:
    a step's score is the chosen token's logit; ``expert_load`` is
    ``(prefill [expert layers, E], decode [steps - 1, expert layers,
    E])`` tokens per expert; the state comes back so that the donated
    buffers are the ones the loop writes (and a caller with room left
    can go on from it)."""
    def pick(logits):
        return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                jnp.max(logits, axis=-1))

    def step(carry, _):
        state, tok = carry
        logits, state, load = _decode_step(w, state, tok, cfg)
        tok, score = pick(logits)
        return (state, tok), (tok, score, load)

    tok0, score0 = pick(first)
    (state, _), (toks, scores, loads) = jax.lax.scan(
        step, (state, tok0), None, length=steps - 1)
    tokens = jnp.concatenate([tok0[None], toks], axis=0).T
    scores = jnp.concatenate([score0[None], scores], axis=0).T
    return tokens, scores, (state["load"], loads), state
