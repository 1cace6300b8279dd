"""The Pallas kernels compiled at the benchmark's widths for a
DESCRIBED TPU v5e (the chip's compiler is installed here; nothing
runs): what Pallas' interpreter cannot refuse, the chip's compiler
does here and not on the chip: a block that does not fit the tiling,
more VMEM than a kernel may use. The topology is described inside a
fixture of this one file (on-chip-measurement guide, section 2)."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from predictionio_tpu.ops import (
    head_lanes, hyper_mix, moe, ssm_scan, window_attention as wa)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever the plugin raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def for_the_chip(monkeypatch):
    """The kernel asks the backend whether to run interpreted; here the
    backend is the CPU and the target is not."""
    monkeypatch.setattr(moe, "_interpreted", lambda: False)
    monkeypatch.setattr(wa, "_interpreted", lambda: False)
    monkeypatch.setattr(hyper_mix, "_interpreted", lambda: False)
    monkeypatch.setattr(head_lanes, "_interpreted", lambda: False)


def _compiled(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("rows,top_k,experts,width,inner,tiles", [
    (16, 8, 256, 2048, 512, 1),    # the laguna cell's decode step
    (1, 8, 256, 2048, 512, 1),     # one row, padded to a sublane tile
    (8, 4, 32, 2048, 1792, 7),     # lfm2_moe's experts: 7 tiles of 256
    (4, 4, 64, 3584, 1024, 4),     # the xing4 cell's step: 4 tiles of 256
], ids=["laguna-16x8", "laguna-1x8", "lfm2-8x4", "xing4-4x4"])
def test_the_touched_experts_kernel_compiles_at_the_cells_widths(
        one_chip, for_the_chip, rows, top_k, experts, width, inner, tiles):
    bf16 = jnp.bfloat16
    assert moe.product_form(rows, top_k, experts) == moe.TOUCHED
    assert moe._f_tiles(width, inner, 2) == tiles
    compiled = _compiled(
        moe._touched_experts, one_chip,
        ((rows, width), bf16), ((rows, top_k), jnp.int32),
        ((rows, top_k), jnp.float32), ((experts, width, inner), bf16),
        ((experts, width, inner), bf16), ((experts, inner, width), bf16))
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "touched_experts" in text


#: the attention kernel's operands at the three generative cells' widths:
#: (head width, window, [q, k, v(, q2, k2)] as ``[G, rows, slots, lanes]``)
ATTENTION = {
    # laguna-xs2-l5: 128-wide heads in the projections' lanes, the
    # queries in two groups of 24 (a full layer's 48) or 32 (a sliding
    # layer's 64), the 8 key-value heads in one
    "laguna-full-48-over-8": (128, None, [
        (2, 16, 4096, 24 * 128), (1, 16, 4096, 1024), (1, 16, 4096, 1024)]),
    "laguna-sliding-64-over-8": (128, 512, [
        (2, 16, 4096, 32 * 128), (1, 16, 4096, 1024), (1, 16, 4096, 1024)]),
    # xing4-29b-a4b-l6, latent attention expanded: 32 heads' nope halves
    # and values 128 wide in the lanes, the rotated halves (64) padded
    # to a lane tile against ONE rotated key for all heads
    "xing4-128-and-64-rope-x32": (128, None, [
        (1, 4, 4096, 4096), (1, 4, 4096, 4096), (1, 4, 4096, 4096),
        (1, 4, 4096, 4096), (1, 4, 4096, 128)]),
    # the same heads first, the rotated halves at their own 64: what a
    # model whose nope half does not fill a lane tile takes
    "xing4-heads-first-rope-64": (128, None, [
        (32, 4, 4096, 128), (32, 4, 4096, 128), (32, 4, 4096, 128),
        (32, 4, 4096, 64), (1, 4, 4096, 64)]),
    # lfm2-8b-a1b-l14: 64-wide heads do not fill a lane tile: a head a
    # group, the layout the kernel had until PR 38
    "lfm2-64-x32-over-8": (64, None, [
        (32, 64, 512, 64), (8, 64, 512, 64), (8, 64, 512, 64)]),
}


@pytest.mark.parametrize("case", sorted(ATTENTION))
def test_the_attention_kernel_compiles_at_the_cells_widths(
        one_chip, for_the_chip, case):
    import functools

    D, window, shapes = ATTENTION[case]
    bf16 = jnp.bfloat16
    compiled = _compiled(
        functools.partial(wa.window_attention.__wrapped__, scale=0.125,
                          window=window, block=wa.BLOCK, head_dim=D),
        one_chip, *((s, bf16) for s in shapes[:3]),
        ((shapes[0][1],), jnp.int32), *((s, bf16) for s in shapes[3:]))
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "window_attention" in text


def test_heads_narrower_than_a_lane_tile_cannot_lie_in_the_lanes(
        one_chip, for_the_chip):
    """Why ``models/decoder.py::_groups`` asks the width: 64-wide heads
    side by side in the lanes are blocks of half a lane tile, which the
    chip's compiler refuses (and Pallas' interpreter does not)."""
    import functools

    bf16 = jnp.bfloat16
    with pytest.raises(Exception, match="divisible by 8 and 128"):
        _compiled(
            functools.partial(wa.window_attention.__wrapped__, scale=0.125,
                              window=None, block=wa.BLOCK, head_dim=64),
            one_chip, ((1, 64, 512, 32 * 64), bf16),
            ((1, 64, 512, 8 * 64), bf16), ((1, 64, 512, 8 * 64), bf16),
            ((64,), jnp.int32))


@pytest.mark.parametrize("heads,rotated", [(16, 128), (24, 64), (8, 128)],
                         ids=["sliding-16-of-64", "full-24-of-48-half",
                              "the-8-key-heads"])
def test_the_head_kernels_compile_at_the_laguna_cells_widths(
        one_chip, for_the_chip, heads, rotated):
    """128-wide heads side by side in the lanes of a 32,768-slot
    stream: a group of a layer's queries (16 of a sliding layer's 64,
    24 of a full layer's 48, which rotates half a head) or its 8 keys
    through ``head_norm_rotary``, the attention's output through
    ``head_gate``."""
    import functools

    import numpy as np

    T, D, f32 = 32768, 128, jnp.float32
    rope = (tuple(1.0 / 1e4 ** (np.arange(0, rotated, 2) / rotated)), 1.0)
    text = _compiled(
        functools.partial(head_lanes.head_norm_rotary.__wrapped__, rope=rope,
                          head_dim=D, eps=1e-6, dtype="bfloat16"),
        one_chip, ((T, heads * D), f32), ((D,), f32),
        ((T,), jnp.int32)).as_text()
    assert "tpu_custom_call" in text and "head_norm_rotary" in text
    text = _compiled(
        functools.partial(head_lanes.head_gate.__wrapped__, head_dim=D),
        one_chip, ((T, heads * D), jnp.bfloat16), ((T, heads), f32)).as_text()
    assert "tpu_custom_call" in text and "head_gate" in text


#: a dispatch's prefill at the generative cells' most frequent shapes:
#: (configuration, rows, stream slots, history, the most MiB that
#: ``copy`` instructions outside fusions may write in all). By this
#: file's count (an instruction once, whatever the trips of the loop it
#: stands in) the parent of PR 39 wrote 12,654 MiB at laguna's 32,768
#: rung (88 % of its cell's batches), 6,471 at xing4's 12,288 and 1,517
#: at lfm2's 16,384; this tree 370, 2,076 (the streams' prefetches into
#: the memory space the gathers read from, the weights) and 545. The
#: ceilings of the two cells whose heads take the lanes are under a
#: third and a half of the parent's; lfm2's, whose 64-wide heads keep a
#: head a group, is the parent's reading
PREFILLS = {
    "laguna-16x32768": ("laguna-xs2-l5", 16, 32768, 4096, 2048),
    "xing4-4x12288": ("xing4-29b-a4b-l6", 4, 12288, 4096, 3072),
    "lfm2-64x16384": ("lfm2-8b-a1b-l14", 64, 16384, 512, 1517),
}
#: MiB that ``copy`` / ``transpose`` instructions beside a
#: ``window_attention`` call may write in all. The parent of PR 39 wrote
#: over 10,000 (laguna) and 3,840 (xing4) there; this tree writes 0
BESIDE_THE_KERNEL_MB = 64
_PREFILL = {}  # case -> (cfg, the optimised text): one compile a case


def _instructions(text):
    """``{name: (opcode, result bytes, operand names)}`` of the optimised
    HLO's instructions outside fusions' bodies."""
    import re

    size = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1, "s8": 1}
    out, inside = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            inside = head.group(1)
            continue
        m = re.match(r"^\s+(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* "
                     r"([\w\-]+)\((.*)$", line)
        if not m or (inside or "").startswith("fused_"):
            continue
        name, dtype, dims, opcode, rest = m.groups()
        n = size.get(dtype, 4)
        for x in filter(None, dims.split(",")):
            n *= int(x)
        if opcode == "custom-call" and "window_attention" in rest:
            opcode = "window_attention"
        out[name] = (opcode, n, re.findall(r"%([\w.\-]+)",
                                           rest.split("), ")[0]))
    return out


def _prefill(one_chip, case):
    """``(cfg, instructions)`` of ``_gen_prefill`` compiled for the
    described v5e at ``PREFILLS[case]`` (under ``for_the_chip``)."""
    import json
    import os

    from predictionio_tpu.models import decoder

    if case not in _PREFILL:
        name, rows, slots, history, _ = PREFILLS[case]
        with open(os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "cellbench", "configs",
                name + ".json")) as f:
            cfg = decoder.DecoderConfig.from_dict(json.load(f))
        w = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            jax.eval_shape(
                lambda: decoder.init_weights(jax.random.key(0), cfg)))
        text = decoder._gen_prefill.lower(
            w, jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip),
            cfg=cfg, history=history, room=32).compile().as_text()
        _PREFILL[case] = cfg, _instructions(text)
    return _PREFILL[case]


@pytest.mark.parametrize("case", sorted(PREFILLS))
def test_a_prefill_s_copies_stay_under_their_ceiling(
        one_chip, for_the_chip, case):
    """What ``prefill_copy_device_ms`` reads on the chip, held without
    one: the bytes the ``copy`` instructions of the optimised
    ``_gen_prefill`` write (layout changes XLA puts between operations;
    a fusion computes). A projection written by head, a gather along a
    middle axis or a reshape across a memory tile on the attention's
    path shows here as gigabytes (docs/kernels.md)."""
    _, ins = _prefill(one_chip, case)
    copied = sum(n for op, n, _ in ins.values() if op == "copy")
    assert 0 < copied <= PREFILLS[case][4] << 20, copied >> 20


@pytest.mark.parametrize("case", ["laguna-16x32768", "xing4-4x12288"])
def test_nothing_changes_a_layout_beside_the_attention_kernel(
        one_chip, for_the_chip, case):
    """``_gen_prefill`` compiled for the described v5e: between the
    gathers into rows and the kernel, and between the kernel and the
    gather back into the stream, no ``copy`` or ``transpose`` stands
    (through bitcasts and tuple plumbing): the kernel reads and writes
    the layout the projections have. The state's transpose into the
    decode's ``[B, kv heads, slots, D]`` is a fusion off the gathered
    keys and values (``_cache_layout``), not on the kernel's path, and
    is exempt. (``lfm2-8b-a1b-l14``'s 64-wide heads keep a head a
    group and the copies that come with it.)"""
    cfg, ins = _prefill(one_chip, case)
    users = {}
    for name, (_, _, operands) in ins.items():
        for o in operands:
            users.setdefault(o, []).append(name)
    plumbing = ("bitcast", "get-tuple-element", "tuple", "reshape")

    def through(name, step):  # the nearest instructions that do work
        found, todo = set(), list(step(name))
        while todo:
            n = todo.pop()
            if ins.get(n, ("?",))[0] in plumbing:
                todo += step(n)
            else:
                found.add(n)
        return found

    kernels = [n for n, (op, _, _) in ins.items() if op == "window_attention"]
    assert len(kernels) == cfg.num_hidden_layers
    beside = set()
    for k in kernels:
        beside |= through(k, lambda n: ins[n][2] if n in ins else [])
        beside |= through(k, lambda n: users.get(n, []))
    moved = {n: ins[n][1] for n in beside
             if n in ins and ins[n][0] in ("copy", "transpose")}
    assert sum(moved.values()) <= BESIDE_THE_KERNEL_MB << 20, moved


@pytest.mark.parametrize("slots", [4096, 16384])
def test_the_read_kernel_compiles_at_the_xing4_cells_rungs(
        one_chip, for_the_chip, slots):
    """Four float32 streams 3584 wide in tiles of 128 tokens, ``phi``'s
    three bfloat16 parts resident, within the VMEM the kernel asks; and
    the parts are still rounded apart in what the compiler made (a cast
    there and back it would have dropped, with two of the three)."""
    import functools

    f32, n, H = jnp.float32, 4, 3584
    c = n * (n + 2)
    compiled = _compiled(
        functools.partial(hyper_mix.hyper_mix_read.__wrapped__, eps=1e-6,
                          norm_eps=1e-6, clamp=(-30.0, 30.0), iters=20),
        one_chip, ((n, slots, H), f32), ((n * H, c), f32), ((c,), f32),
        ((c,), f32), ((H,), f32))
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "hyper_mix_read" in text
    assert "reduce-precision" in text


@pytest.mark.parametrize("slots", [4096, 16384])
def test_the_write_kernel_compiles_onto_its_input(one_chip, for_the_chip,
                                                  slots):
    """The stacked ``[4, slots, 3584]`` comes out in the buffer the
    streams came in: no second 0.94 GB at 16,384 slots."""
    f32, n, H = jnp.float32, 4, 3584
    args = [jax.ShapeDtypeStruct(s, f32, sharding=one_chip) for s in (
        (n, slots, H), (slots, H), (slots, hyper_mix.LANES))]
    compiled = jax.jit(hyper_mix.hyper_mix_write.__wrapped__,
                       donate_argnums=(0,)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "hyper_mix_write" in text
    assert compiled.memory_analysis().alias_size_in_bytes \
        == n * slots * H * 4


@pytest.mark.parametrize("slots", [2048, 16384])
def test_the_scan_kernel_compiles_at_the_granite_cells_rungs(one_chip,
                                                             slots):
    """64 heads of 64 over a state of 128 in chunks of 256, 16 rows: the
    shortest and the longest stream of the cell's ladder."""
    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    compiled = _compiled(
        lambda *a: ssm_scan.scan_kernel(*a, chunk=256), one_chip,
        ((slots, 4096), bf16), ((slots, 128), bf16), ((slots, 128), bf16),
        ((slots, 64), f32), ((64,), f32), ((slots,), i32), ((16,), i32))
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "ssm_scan" in text


def test_the_step_kernel_compiles_onto_its_state(one_chip):
    """16 rows' float32 states ``[128, 4096]``: the new state lands in
    the buffer the old one came in (donated: no second 32 MiB)."""
    f32 = jnp.float32
    args = [jax.ShapeDtypeStruct(s, f32, sharding=one_chip) for s in (
        (16, 128, 4096), (16, 4096), (16, 128), (16, 128), (16, 64),
        (16, 64))]
    compiled = jax.jit(ssm_scan.step_kernel, donate_argnums=(0,)).lower(
        *args).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "ssm_step" in text
    assert compiled.memory_analysis().alias_size_in_bytes \
        == 16 * 128 * 4096 * 4


@pytest.mark.parametrize("slots", [2048, 16384])
def test_the_grouped_scan_kernel_compiles_at_the_nemotron_cells_rungs(
        one_chip, slots):
    """128 heads of 64 in 8 groups over a state of 128 in chunks of 128,
    16 rows: a grid step's 8 heads read their own group's ``B`` and
    ``C`` (two steps a group)."""
    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    assert ssm_scan.kernel_takes(128, 64, 128, 8)
    compiled = _compiled(
        lambda *a: ssm_scan.scan_kernel(*a, chunk=128, groups=8), one_chip,
        ((slots, 8192), bf16), ((slots, 1024), bf16), ((slots, 1024), bf16),
        ((slots, 128), f32), ((128,), f32), ((slots,), i32), ((16,), i32))
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "ssm_scan" in text


def test_the_grouped_step_kernel_compiles_onto_its_state(one_chip):
    """16 rows' float32 states ``[128, 8192]`` in 8 groups: a grid
    step's 1,024 lanes are one group's, and the new state lands in the
    buffer the old one came in (no second 64 MiB)."""
    f32 = jnp.float32
    args = [jax.ShapeDtypeStruct(s, f32, sharding=one_chip) for s in (
        (16, 128, 8192), (16, 8192), (16, 1024), (16, 1024), (16, 128),
        (16, 128))]
    compiled = jax.jit(ssm_scan.step_kernel, donate_argnums=(0,)).lower(
        *args).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "ssm_step" in text
    assert compiled.memory_analysis().alias_size_in_bytes \
        == 16 * 128 * 8192 * 4


def test_the_plain_experts_kernel_compiles_at_the_nemotron_cells_widths(
        one_chip, for_the_chip):
    """The cell's decode step: 16 rows of the 1,024-wide latent, 22 of
    512 experts a token of which 128 are held, an expert's two matrices
    1024 x 2688 in 3 tiles of 896."""
    bf16 = jnp.bfloat16
    assert moe.product_form(16, 22, 128, 512) == moe.TOUCHED
    assert moe._f_tiles(1024, 2688, 2, 2) == 3
    compiled = _compiled(
        lambda x, local, wts, w1, w2: moe._touched_experts(
            x, local, wts, w1, None, w2), one_chip,
        ((16, 1024), bf16), ((16, 22), jnp.int32), ((16, 22), jnp.float32),
        ((128, 1024, 2688), bf16), ((128, 2688, 1024), bf16))
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "touched_experts" in text


@pytest.mark.parametrize("batch", [1, 2, 4, 8, 16, 32, 64, 128])
def test_the_serving_program_holds_one_array_of_scores(one_chip, monkeypatch,
                                                       batch):
    """``_serve_topk`` over the ALS cells' float32 tables (4,847,571
    items, a multiple of no chunk, rank 128, ``k_dev`` 16) for the
    described v5e: the chunk maxima are the ``chunk_maxima`` kernel, the
    only ``top_k``s left are the two small ones, and the program's
    temporaries are the ``[B, n_pad]`` scores once: no padded, sliced or
    re-laid-out second copy (every plain-XLA form of the maxima made
    one at ``B`` 32 or 128, PERF.md finding 44.1)."""
    from predictionio_tpu.models import als

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, r, k = 4_847_571, 128, 16
    table = jax.ShapeDtypeStruct((n, r), jnp.float32, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one_chip)
    compiled = als._serve_topk.lower(
        table, table, idx, k=k, n_items=n).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "chunk_maxima" in text
    shape = dict(re.findall(r"%([\w.\-]+) = (\w+\[[\d,]*\])", text))
    selected = re.findall(
        r'custom-call\(%([\w.\-]+)\), custom_call_target="TopK"', text)
    widths = sorted(int(re.findall(r"\d+", shape[o])[-1]) for o in selected)
    assert widths == sorted([-(-n // als.SELECT_CHUNK),
                             k * als.SELECT_CHUNK]), widths
    scores = batch * n * 4
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= scores + (32 << 20)
