"""The Pallas kernels compiled at the benchmark's widths for a
DESCRIBED TPU v5e (the chip's compiler is installed here; nothing
runs): what Pallas' interpreter cannot refuse, the chip's compiler
does here and not on the chip: a block that does not fit the tiling,
more VMEM than a kernel may use. The topology is described inside a
fixture of this one file (on-chip-measurement guide, section 2)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from predictionio_tpu.ops import hyper_mix, moe, window_attention as wa


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


def test_the_attention_kernel_compiles_with_values_narrower_than_keys(
        one_chip, for_the_chip):
    """Latent attention expanded, at the xing4 cell's widths: 32 heads
    on both sides, queries and keys 192 wide (not a multiple of 128
    lanes) against values 128 wide, 4 rows of 4,096 slots."""
    import functools

    bf16 = jnp.bfloat16
    compiled = _compiled(
        functools.partial(wa.window_attention.__wrapped__, scale=0.14468,
                          window=None, block=wa.BLOCK),
        one_chip, ((32, 4, 4096, 192), bf16), ((32, 4, 4096, 192), bf16),
        ((32, 4, 4096, 128), bf16), ((4,), jnp.int32))
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "window_attention" in text


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
