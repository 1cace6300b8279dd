"""``ptpu audit-numerics`` — the abstract-eval precision audit.

The static dtype-flow rules (:mod:`.numerics`) catch the narrowings
and upcasts the AST can see; this module catches the ones only the
traced program sees. It abstract-interprets the framework's registered
numeric entry points (``jax.make_jaxpr`` — a jaxpr walk, NO device
execution and no XLA compile) and extracts a per-entry **dtype
census**:

- ``ops`` — primitive-application counts keyed by result dtype;
- ``casts`` — every ``convert_element_type`` site, keyed
  ``src->dst``: the cast inventory. A new ``int8->float32`` or
  ``bfloat16->float32`` cast in a quantized entry is a dequantized
  table copy forfeiting the 4×-users-per-HBM win; a new ``->bfloat16``
  cast is dropped mantissa;
- ``reductions`` — accumulation dtype per reducing primitive
  (``reduce_sum`` / ``dot_general`` / …): the result dtype IS the
  accumulator dtype, so an einsum that loses its
  ``preferred_element_type=jnp.float32`` shows up as a
  ``dot_general`` accumulating at ``bfloat16``;
- ``bytes`` — result bytes by dtype (abstract shapes × itemsize): the
  footprint census that moves when a program starts materializing
  wide buffers.

The census diffs against a committed golden manifest
(``analysis/numerics_baseline.json``) with the same ratchet semantics
as ``audit-hlo``:

- a cast key the baseline entry does not record — or a count above
  the recorded one — FAILS, naming the entry, the cast and the count;
- a reducing primitive accumulating at bf16/f16 beyond the recorded
  count FAILS (an accumulator lost its widening);
- per-dtype bytes above ``BYTES_GROWTH_RATIO`` × recorded (plus a
  fixed slack) fail the same way;
- everything below the record prints as shrinkable and
  ``--write-baseline`` only ever ratchets the file down; recording
  new casts/entries (a deliberate precision change) takes the
  explicit ``--baseline-grow``.

Entry points audited (small shapes — the *dtype structure* is
shape-independent, which is why a golden manifest works): the eight
``audit-hlo`` SPMD entries traced through the same builders' inputs,
plus the three serving-quant seams PR 13 made load-bearing —
``foldin_update_bf16`` (the streaming fold-in's bf16 gather shadow
into :func:`~predictionio_tpu.models.als._update_block`),
``quantize_serving_model`` (the blessed dequant funnel pair), and
``device_topk_{off,bf16,int8}`` (the fused serving dispatch in all
three quant modes).

Everything jax-flavored imports lazily; the CLI pins the forced
8-device CPU topology (:func:`~.hlo_audit.ensure_cpu_devices`) before
the first jax import, because half the entries trace through meshes.

See docs/static-analysis.md ("How to read an audit-numerics diff").
"""

from __future__ import annotations

import json
import os
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from .hlo_audit import AUDIT_DEVICE_COUNT, AuditError, ensure_cpu_devices

MANIFEST_VERSION = 1

#: per-dtype result bytes may grow this factor (plus slack) over the
#: recorded baseline before the gate fails — shape-padding jitter moves
#: bytes a little; a dequantized table copy moves them a lot
BYTES_GROWTH_RATIO = 1.5
BYTES_SLACK = 64 * 1024

DEFAULT_BASELINE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "numerics_baseline.json")

#: accumulation dtypes that fail the gate when a reduction's count
#: grows — a sum/dot accumulating here is a lost f32 widening
LOW_PRECISION = ("bfloat16", "float16", "float8")

#: reducing primitives whose RESULT dtype is the accumulator dtype
REDUCING_PRIMS = frozenset({
    "reduce_sum", "reduce_prod", "dot_general", "cumsum",
    "reduce_window_sum", "cumprod",
})


def _is_low(dtype: str) -> bool:
    return any(dtype.startswith(p) for p in LOW_PRECISION)


# ---------------------------------------------------------------------------
# jaxpr census
# ---------------------------------------------------------------------------

def _sub_jaxprs(params: dict):
    """Inner jaxprs of one equation (pjit/scan/cond/shard_map/…)."""
    from jax.extend import core as jcore

    def _as_jaxpr(v):
        if isinstance(v, jcore.ClosedJaxpr):
            return v.jaxpr
        if isinstance(v, jcore.Jaxpr):
            return v
        return None

    for v in params.values():
        j = _as_jaxpr(v)
        if j is not None:
            yield j
        elif isinstance(v, (list, tuple)):
            for x in v:
                j = _as_jaxpr(x)
                if j is not None:
                    yield j


def census_jaxpr(closed) -> dict:
    """One entry-point record: {ops, casts, reductions, bytes} over a
    ClosedJaxpr, recursing into sub-jaxprs. Call-like equations
    (those CARRYING sub-jaxprs) contribute only their bodies — their
    outvars duplicate the inner results."""
    ops: Dict[str, int] = {}
    casts: Dict[str, int] = {}
    reductions: Dict[str, Dict[str, int]] = {}
    nbytes: Dict[str, int] = {}

    def walk(jaxpr) -> None:
        for eqn in jaxpr.eqns:
            inner = list(_sub_jaxprs(eqn.params))
            if inner:
                for sub in inner:
                    walk(sub)
                continue
            prim = eqn.primitive.name
            out_dts: List[str] = []
            for ov in eqn.outvars:
                aval = getattr(ov, "aval", None)
                dt = getattr(aval, "dtype", None)
                if dt is None:
                    continue
                d = str(dt)
                out_dts.append(d)
                size = int(getattr(aval, "size", 0) or 0)
                nbytes[d] = nbytes.get(d, 0) + size * dt.itemsize
            for d in out_dts:
                ops[d] = ops.get(d, 0) + 1
            if prim == "convert_element_type" and eqn.invars and out_dts:
                src_aval = getattr(eqn.invars[0], "aval", None)
                src = str(getattr(src_aval, "dtype", "?"))
                key = f"{src}->{out_dts[0]}"
                casts[key] = casts.get(key, 0) + 1
            elif prim in REDUCING_PRIMS and out_dts:
                by = reductions.setdefault(prim, {})
                by[out_dts[0]] = by.get(out_dts[0], 0) + 1

    walk(closed.jaxpr)
    return {"ops": ops, "casts": casts, "reductions": reductions,
            "bytes": nbytes}


# ---------------------------------------------------------------------------
# entry-point builders (each returns a jax.extend.core.ClosedJaxpr)
# ---------------------------------------------------------------------------

def _training_mesh():
    from ..parallel.mesh import make_mesh

    return make_mesh()


def _serving_mesh():
    from ..parallel.mesh import make_serving_mesh

    return make_serving_mesh()


def _lhs_arrays(n_dev: int):
    import numpy as np

    table = np.ones((8 * n_dev, 16), np.float32)
    idx = np.zeros((n_dev, 4, 8), np.int32)
    w = np.ones((n_dev, 4, 8), np.float32)
    return table, idx, w


def _entry_gramian_allreduce():
    import jax
    import numpy as np

    from ..parallel.collectives import gramian_allreduce

    mesh = _training_mesh()
    x = np.ones((8 * mesh.devices.size, 16), np.float32)
    return jax.make_jaxpr(lambda t: gramian_allreduce(t, mesh))(x)


def _entry_gather_rows():
    import jax
    import numpy as np

    from ..models.als import _gather_rows_fn

    mesh = _serving_mesh()
    table = np.ones((8 * mesh.devices.size, 16), np.float32)
    idx = np.zeros((4,), np.int64)
    return jax.make_jaxpr(_gather_rows_fn(mesh))(table, idx)


def _entry_sharded_rank():
    import jax
    import numpy as np

    from ..models.als import _sharded_rank_fn

    mesh = _serving_mesh()
    n = 8 * mesh.devices.size
    table = np.ones((n, 16), np.float32)
    vecs = np.ones((4, 16), np.float32)
    fn = _sharded_rank_fn(mesh, 8, 8, n)
    return jax.make_jaxpr(fn)(vecs, table)


def _entry_lhs_einsum():
    import functools

    import jax

    from ..models.als import _lhs_fn

    table, idx, w = _lhs_arrays(AUDIT_DEVICE_COUNT)
    fn = functools.partial(_lhs_fn, bf16=False)
    return jax.make_jaxpr(fn)(table, idx, w, w)


def _entry_train_update_block():
    import functools

    import jax
    import numpy as np

    from ..models.als import _update_block

    table, idx, w = _lhs_arrays(AUDIT_DEVICE_COUNT)
    counts = np.ones((AUDIT_DEVICE_COUNT, 4), np.float32)
    G = np.zeros((16, 16), np.float32)
    fn = functools.partial(
        _update_block.__wrapped__, implicit=True, scale_reg=True,
        bf16=False, mesh=None)
    return jax.make_jaxpr(fn)(table, G, idx, w, counts, 0.1, 40.0)


def _entry_seqrec_train_step():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models.seqrec import SeqRecParams, _init_weights, _train_step

    p = SeqRecParams(dim=16, heads=2, max_len=8, n_negatives=4,
                     batch_size=8)
    w = _init_weights(jax.random.key(0), 32, p)
    m = {k: jnp.zeros_like(v) for k, v in w.items()}
    v = {k: jnp.zeros_like(v) for k, v in w.items()}
    seq = np.zeros((8, 8), np.int32)
    fn = jax.make_jaxpr(_train_step, static_argnums=(6, 7))
    return fn(w, m, v, jnp.zeros((), jnp.int32), seq,
              jax.random.key(1), p, 32)


def _entry_sharded_topk():
    import jax
    import numpy as np

    from ..parallel.collectives import sharded_top_k
    from ..parallel.mesh import make_mesh

    mesh = make_mesh(data=2, model=4)
    scores = np.ones((4, 64), np.float32)
    return jax.make_jaxpr(
        lambda s: sharded_top_k(s, 8, mesh, axis="model"))(scores)


def _entry_foldin_update_bf16():
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models.als import _update_block

    table, idx, w = _lhs_arrays(1)
    counts = np.ones((1, 4), np.float32)
    G = np.zeros((16, 16), np.float32)
    inner = functools.partial(
        _update_block.__wrapped__, implicit=True, scale_reg=True,
        bf16=True, mesh=None)

    def fold_block(table, G, idx, w, counts):
        # the fold_in_rows seam verbatim: gather_dtype="bfloat16"
        # shadows the fixed table INTO the gather, accumulation f32
        return inner(table.astype(jnp.bfloat16), G, idx, w, counts,
                     0.1, 40.0)

    return jax.make_jaxpr(fold_block)(table, G, idx, w, counts)


def _entry_quantize_serving_model():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models.als import _dequant_plain, _dequant_scaled

    data = np.zeros((64, 16), np.int8)
    scale = np.ones((64, 1), np.float32)
    bdata = jnp.zeros((64, 16), jnp.bfloat16)

    def funnels(data, scale, bdata):
        # the two blessed dequant funnels quantize_serving_model's
        # consumers route through
        return _dequant_scaled(data, scale), _dequant_plain(bdata)

    return jax.make_jaxpr(funnels)(data, scale, bdata)


def _topk_tables(quant: str):
    import jax.numpy as jnp
    import numpy as np

    from ..models.als import QuantizedFactors

    u = np.ones((32, 16), np.float32)
    v = np.ones((64, 16), np.float32)
    if quant == "off":
        return u, v
    if quant == "bf16":
        def mk(a):
            # ptpu: allow[quantize-without-parity-gate] — audit
            # fixture on a synthetic all-ones table; nothing serves it
            return QuantizedFactors(jnp.asarray(a, jnp.bfloat16),
                                    None, "bf16")
    else:
        def mk(a):
            # ptpu: allow[quantize-without-parity-gate] — audit
            # fixture on a synthetic all-ones table; nothing serves it
            return QuantizedFactors(
                np.ones(a.shape, np.int8),
                np.ones((a.shape[0], 1), np.float32), "int8")
    return mk(u), mk(v)


def _entry_device_topk(quant: str):
    import jax
    import numpy as np

    from ..models.als import _serve_topk

    u, v = _topk_tables(quant)
    idx = np.zeros((4,), np.int32)
    fn = jax.make_jaxpr(
        lambda uf, vf, i: _serve_topk(uf, vf, i, k=8, n_items=60))
    return fn(u, v, idx)


def _gen_small():
    """The generative programs' inputs at the CPU tests' size, in the
    served dtype (bfloat16 weights and operands)."""
    import jax
    import numpy as np

    from ..models import decoder

    cfg = decoder.DecoderConfig(
        hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
        num_hidden_layers=4,
        layer_types=("conv", "full_attention", "conv", "conv"),
        num_dense_layers=1, num_attention_heads=4, num_key_value_heads=2,
        num_experts=8, num_experts_per_tok=2, vocab_size=256)
    w = decoder.init_weights(jax.random.key(0), cfg)
    tokens = np.zeros((64,), np.int32)  # 4 rows of 9, packed; 28 spare
    lengths = np.full((4,), 9, np.int32)
    return decoder, cfg, w, tokens, lengths


def _entry_gen_prefill():
    import jax

    decoder, cfg, w, tokens, lengths = _gen_small()
    return jax.make_jaxpr(
        lambda w, t, n: decoder._gen_prefill(w, t, n, cfg=cfg, history=16,
                                             room=4)
    )(w, tokens, lengths)


def _entry_gen_decode():
    import jax

    decoder, cfg, w, tokens, lengths = _gen_small()
    first, state = decoder._gen_prefill(w, tokens, lengths, cfg=cfg,
                                        history=16, room=4)
    return jax.make_jaxpr(
        lambda w, s, f: decoder._gen_decode(w, s, f, cfg=cfg, steps=4)
    )(w, state, first)


def _gen_laguna_small():
    """The ``laguna`` family's programs at a small size in the served
    dtype: a full and a sliding layer (window 8, 4 and 6 query heads
    over 2 key-value heads, yarn over half a head), the head gate, a
    shared expert, an untied head."""
    import jax
    import numpy as np

    from ..models import decoder

    cfg = decoder.DecoderConfig.from_dict({
        "hidden_size": 64, "intermediate_size": 128,
        "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
        "num_hidden_layers": 2,
        "layer_types": ["full_attention", "sliding_attention"],
        "mlp_layer_types": ["dense", "sparse"],
        "num_attention_heads": 4, "num_attention_heads_per_layer": [4, 6],
        "num_key_value_heads": 2, "head_dim": 16, "num_experts": 8,
        "num_experts_per_tok": 2, "vocab_size": 256, "sliding_window": 8,
        "gating": True, "tie_word_embeddings": False,
        "rms_norm_eps": 1e-6, "moe_routed_scaling_factor": 2.5,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                "original_max_position_embeddings": 16, "beta_slow": 1,
                "beta_fast": 4, "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 10000}}})
    w = decoder.init_weights(jax.random.key(0), cfg)
    tokens = np.zeros((64,), np.int32)  # 4 rows of 9, packed; 28 spare
    lengths = np.full((4,), 9, np.int32)
    return decoder, cfg, w, tokens, lengths


def _entry_gen_prefill_laguna():
    import jax

    decoder, cfg, w, tokens, lengths = _gen_laguna_small()
    return jax.make_jaxpr(
        lambda w, t, n: decoder._gen_prefill(w, t, n, cfg=cfg, history=16,
                                             room=4)
    )(w, tokens, lengths)


def _entry_gen_decode_laguna():
    import jax

    decoder, cfg, w, tokens, lengths = _gen_laguna_small()
    first, state = decoder._gen_prefill(w, tokens, lengths, cfg=cfg,
                                        history=16, room=4)
    return jax.make_jaxpr(
        lambda w, s, f: decoder._gen_decode(w, s, f, cfg=cfg, steps=4)
    )(w, state, first)


def _gen_xing_small():
    """The ``xing4_0`` family's programs at a small size in the served
    dtype: two latent-attention layers (ranks 24 / 16, a head 16 + 8
    wide against values 16 wide, yarn on the softmax) inside four
    residual streams under hyper-connections, a dense and a sparse
    feed-forward with a shared expert, an untied head. Its float32
    islands beside bfloat16 products: the streams, the coefficients'
    projection, sigmoids, exponential and Sinkhorn passes, the latents'
    norms."""
    import jax
    import numpy as np

    from ..models import decoder

    cfg = decoder.DecoderConfig.from_dict({
        "hidden_size": 64, "intermediate_size": 128,
        "moe_intermediate_size": 32, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16,
        "first_k_dense_replace": 1, "n_routed_experts": 8,
        "num_experts_per_tok": 2, "n_shared_experts": 1,
        "routed_scaling_factor": 2, "vocab_size": 256,
        "tie_word_embeddings": False, "rms_norm_eps": 1e-6,
        "rope_theta": 10000,
        "rope_scaling": {"type": "yarn", "factor": 64,
                         "original_max_position_embeddings": 16,
                         "beta_fast": 4, "beta_slow": 1, "mscale": 1,
                         "mscale_all_dim": 1},
        "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
        "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30})
    w = decoder.init_weights(jax.random.key(0), cfg)
    tokens = np.zeros((64,), np.int32)  # 4 rows of 9, packed; 28 spare
    lengths = np.full((4,), 9, np.int32)
    return decoder, cfg, w, tokens, lengths


def _entry_gen_prefill_xing():
    import jax

    decoder, cfg, w, tokens, lengths = _gen_xing_small()
    return jax.make_jaxpr(
        lambda w, t, n: decoder._gen_prefill(w, t, n, cfg=cfg, history=16,
                                             room=4)
    )(w, tokens, lengths)


def _entry_gen_decode_xing():
    import jax

    decoder, cfg, w, tokens, lengths = _gen_xing_small()
    first, state = decoder._gen_prefill(w, tokens, lengths, cfg=cfg,
                                        history=16, room=4)
    state.pop("sinkhorn_gap")  # beside the state, not of it
    return jax.make_jaxpr(
        lambda w, s, f: decoder._gen_decode(w, s, f, cfg=cfg, steps=4)
    )(w, state, first)


#: name → (builder, one-line description); ordered — the manifest and
#: the CI artifact list entries in this order
ENTRY_POINTS: Dict[str, Tuple[Callable[[], object], str]] = {
    "gramian_allreduce": (
        _entry_gramian_allreduce,
        "explicit per-shard Gramian partial + ICI psum"),
    "gather_rows": (
        _entry_gather_rows,
        "cross-shard user-row fetch"),
    "sharded_rank": (
        _entry_sharded_rank,
        "per-shard top-k + candidate all-gather (einsum ranker)"),
    "lhs_einsum": (
        _entry_lhs_einsum,
        "_lhs_fn normal-equation build"),
    "train_update_block": (
        _entry_train_update_block,
        "one ALS training block (gather+Gramian+solve)"),
    "seqrec_train_step": (
        _entry_seqrec_train_step,
        "sequential-model Adam step"),
    "sharded_topk": (
        _entry_sharded_topk,
        "two-phase global top-k over the (data=2, model=4) mesh"),
    "foldin_update_bf16": (
        _entry_foldin_update_bf16,
        "streaming fold-in solve under the bf16 gather shadow"),
    "quantize_serving_model": (
        _entry_quantize_serving_model,
        "the blessed dequant funnel pair (scaled int8 + plain bf16)"),
    "device_topk_off": (
        lambda: _entry_device_topk("off"),
        "fused serving dispatch (_serve_topk), plain f32 tables"),
    "device_topk_bf16": (
        lambda: _entry_device_topk("bf16"),
        "fused serving dispatch, bf16 tables (in-program upcast)"),
    "device_topk_int8": (
        lambda: _entry_device_topk("int8"),
        "fused serving dispatch, int8+scale tables"),
    "gen_prefill": (
        _entry_gen_prefill,
        "generative prefill (models/decoder._gen_prefill), bf16 weights"),
    "gen_decode": (
        _entry_gen_decode,
        "generative greedy decode (models/decoder._gen_decode)"),
    "gen_prefill_laguna": (
        _entry_gen_prefill_laguna,
        "generative prefill of the laguna family (window attention "
        "kernel, head gate, shared expert, untied head), bf16 weights"),
    "gen_decode_laguna": (
        _entry_gen_decode_laguna,
        "generative greedy decode of the laguna family (rings beside "
        "caches)"),
    "gen_prefill_xing": (
        _entry_gen_prefill_xing,
        "generative prefill of the xing4_0 family (latent attention "
        "expanded, four float32 residual streams, Sinkhorn-normalised "
        "hyper-connections), bf16 weights"),
    "gen_decode_xing": (
        _entry_gen_decode_xing,
        "generative greedy decode of the xing4_0 family (absorbed "
        "attention over the latent cache)"),
}


def run_audit(names: Optional[Sequence[str]] = None) -> dict:
    """Trace + census every (selected) entry point; returns the
    manifest dict. Needs the forced device count — half the entries
    trace through 8-device meshes."""
    import jax

    n_dev = len(jax.devices())
    if n_dev < AUDIT_DEVICE_COUNT:
        raise AuditError(
            f"audit-numerics needs {AUDIT_DEVICE_COUNT} devices, found "
            f"{n_dev}; run in a fresh process (the CLI forces "
            f"XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{AUDIT_DEVICE_COUNT} before importing jax)")
    unknown = set(names or ()) - set(ENTRY_POINTS)
    if unknown:
        raise AuditError(f"unknown entry point(s): {sorted(unknown)} "
                         f"(have: {sorted(ENTRY_POINTS)})")
    entries: Dict[str, dict] = {}
    for name, (builder, _desc) in ENTRY_POINTS.items():
        if names and name not in names:
            continue
        entries[name] = census_jaxpr(builder())
    return {"version": MANIFEST_VERSION,
            "devices": AUDIT_DEVICE_COUNT,
            "entries": entries}


# ---------------------------------------------------------------------------
# manifest I/O + ratchet diff
# ---------------------------------------------------------------------------

def load_manifest(path: str) -> dict:
    with open(path, "r", encoding="utf-8") as fh:
        doc = json.load(fh)
    if not isinstance(doc, dict) \
            or doc.get("version") != MANIFEST_VERSION:
        raise ValueError(f"{path}: not an audit-numerics manifest "
                         f"(expected version {MANIFEST_VERSION})")
    return doc


def _clamp_counts(new: Dict[str, int], old: Dict[str, int]
                  ) -> Dict[str, int]:
    return {k: min(c, old[k]) for k, c in new.items() if k in old}


def write_manifest(path: str, manifest: dict,
                   cap: Optional[dict] = None) -> None:
    """Persist the manifest. With ``cap`` (the previously committed
    baseline) the write RATCHETS: entries/keys the old baseline never
    held are dropped and counts/bytes clamp to the recorded values —
    the file only shrinks (``--baseline-grow`` writes as-is)."""
    doc = manifest
    if cap is not None:
        old = cap.get("entries", {})
        entries: Dict[str, dict] = {}
        for name, rec in manifest.get("entries", {}).items():
            if name not in old:
                continue
            orec = old[name]
            oreds = orec.get("reductions", {})
            reds = {prim: _clamp_counts(by, oreds[prim])
                    for prim, by in rec.get("reductions", {}).items()
                    if prim in oreds}
            entries[name] = {
                "ops": _clamp_counts(rec.get("ops", {}),
                                     orec.get("ops", {})),
                "casts": _clamp_counts(rec.get("casts", {}),
                                       orec.get("casts", {})),
                "reductions": reds,
                "bytes": _clamp_counts(rec.get("bytes", {}),
                                       orec.get("bytes", {})),
            }
        doc = {"version": MANIFEST_VERSION,
               "devices": manifest.get("devices", AUDIT_DEVICE_COUNT),
               "entries": entries}
    from .baseline import atomic_write_text

    atomic_write_text(
        path, json.dumps(doc, indent=2, sort_keys=True) + "\n")


def diff_manifests(current: dict, baseline: dict
                   ) -> Tuple[List[str], List[str]]:
    """(violations, shrinkable) between a fresh census and the golden
    baseline. Violations name the entry, the op/cast and the counts —
    the line an operator greps for."""
    violations: List[str] = []
    shrinkable: List[str] = []
    if current.get("devices") != baseline.get("devices"):
        violations.append(
            f"device count {current.get('devices')} != baseline "
            f"{baseline.get('devices')} (mesh entries trace "
            f"topology-dependent programs; audit on the forced mesh)")
    cur = current.get("entries", {})
    base = baseline.get("entries", {})
    for name, rec in cur.items():
        brec = base.get(name)
        if brec is None:
            violations.append(
                f"{name}: entry point not in the baseline — record it "
                f"deliberately with --write-baseline --baseline-grow")
            continue
        bcasts = brec.get("casts", {})
        for key, c in sorted(rec.get("casts", {}).items()):
            b = bcasts.get(key, 0)
            if c > b:
                violations.append(
                    f"{name}: cast {key} x{c} (baseline {b}) — a new "
                    f"convert_element_type in the traced program. An "
                    f"upcast of quantized data materializes a wide "
                    f"copy (forfeits the serving-quant HBM win); a "
                    f"downcast drops mantissa: find the .astype or "
                    f"implicit promotion feeding this entry, or "
                    f"record deliberately with --baseline-grow")
            elif c < b:
                shrinkable.append(f"{name}: cast {key} recorded {b}, "
                                  f"found {c}")
        for key, b in sorted(bcasts.items()):
            if key not in rec.get("casts", {}):
                shrinkable.append(f"{name}: cast {key} recorded {b}, "
                                  f"found 0")
        breds = brec.get("reductions", {})
        for prim, by in sorted(rec.get("reductions", {}).items()):
            bby = breds.get(prim, {})
            for dt, c in sorted(by.items()):
                b = bby.get(dt, 0)
                if _is_low(dt) and c > b:
                    violations.append(
                        f"{name}: {prim} accumulating at {dt} x{c} "
                        f"(baseline {b}) — a reduction lost its f32 "
                        f"accumulator; restore "
                        f"preferred_element_type=jnp.float32 (the "
                        f"ops/gram.py contract) or record "
                        f"deliberately with --baseline-grow")
                elif c < b:
                    shrinkable.append(f"{name}: {prim}@{dt} recorded "
                                      f"{b}, found {c}")
        bbytes = brec.get("bytes", {})
        for dt, n in sorted(rec.get("bytes", {}).items()):
            b = bbytes.get(dt, 0)
            if n > b * BYTES_GROWTH_RATIO + BYTES_SLACK:
                violations.append(
                    f"{name}: {dt} result traffic {n}B vs baseline "
                    f"{b}B (> x{BYTES_GROWTH_RATIO} + {BYTES_SLACK}B "
                    f"slack) — the entry is materializing wider "
                    f"buffers (a dequantized table copy?); or "
                    f"--baseline-grow")
            elif n < b / BYTES_GROWTH_RATIO - BYTES_SLACK:
                shrinkable.append(f"{name}: {dt} bytes recorded {b}, "
                                  f"found {n}")
    for name in base:
        if name not in cur:
            shrinkable.append(f"{name}: entry point no longer audited")
    return violations, shrinkable


def format_text(manifest: dict) -> str:
    lines: List[str] = []
    for name, rec in manifest.get("entries", {}).items():
        ops = rec.get("ops", {})
        summary = ", ".join(f"{dt} x{c}"
                            for dt, c in sorted(ops.items())) \
            or "no ops"
        lines.append(f"{name}: {summary}")
        casts = rec.get("casts", {})
        if casts:
            lines.append("  casts: " + ", ".join(
                f"{k} x{c}" for k, c in sorted(casts.items())))
        for prim, by in sorted(rec.get("reductions", {}).items()):
            lines.append(f"  {prim}: " + ", ".join(
                f"{dt} x{c}" for dt, c in sorted(by.items())))
        low = {dt: n for dt, n in rec.get("bytes", {}).items()
               if _is_low(dt) or dt == "int8"}
        if low:
            lines.append("  low-precision bytes: " + ", ".join(
                f"{dt} {n}B" for dt, n in sorted(low.items())))
    return "\n".join(lines)


__all__ = (
    "AUDIT_DEVICE_COUNT",
    "AuditError",
    "BYTES_GROWTH_RATIO",
    "BYTES_SLACK",
    "DEFAULT_BASELINE",
    "ENTRY_POINTS",
    "LOW_PRECISION",
    "REDUCING_PRIMS",
    "census_jaxpr",
    "diff_manifests",
    "ensure_cpu_devices",
    "format_text",
    "load_manifest",
    "run_audit",
    "write_manifest",
)
