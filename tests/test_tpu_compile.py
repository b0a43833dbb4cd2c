"""Compile rehearsals: the main-path Pallas kernels, compiled for a TPU v5e
chip at published widths, with no chip attached.

The installed TPU compiler compiles for a described topology, so these
tests catch what interpret mode cannot: block shapes Mosaic refuses,
primitives it cannot lower, a ``pallas_call`` that autodiff tries to
linearize.  Nothing runs; each compile takes a second or two.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and every
test worker imports every test file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# qwen2-1.5b decode: 12 query heads over 2 kv heads of width 128
B, H, K, h, S = 8, 12, 2, 128, 4096
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2 host; JAX's persistent compilation
    cache stays off around the compiles (an entry written here could not
    be read back without a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, sharding, *shapes) -> str:
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        for shape, dtype in shapes
    ]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return text


def test_flash_decode_compiles_for_v5e(one_chip):
    from repro.kernels.flash_decode.flash_decode import flash_decode_pallas

    _compiled_text(
        flash_decode_pallas, one_chip,
        ((B, 1, H, h), BF16), ((B, S, K, h), BF16), ((B, S, K, h), BF16),
        ((B,), jnp.int32),
    )


def test_flash_decode_paged_compiles_for_v5e(one_chip):
    from repro.kernels.flash_decode.flash_decode import (
        flash_decode_pallas_paged,
    )

    bs = 16
    nb = S // bs
    pool = (1 + B * nb, bs, K, h)
    _compiled_text(
        flash_decode_pallas_paged, one_chip,
        ((B, 1, H, h), BF16), (pool, BF16), (pool, BF16),
        ((B, nb), jnp.int32), ((B,), jnp.int32),
    )


@pytest.mark.parametrize("batch", [32, 256])
def test_vtrace_compiles_for_v5e(one_chip, batch):
    from repro.kernels.vtrace.vtrace import vtrace_pallas

    bt = ((batch, 20), jnp.float32)
    _compiled_text(
        vtrace_pallas, one_chip, bt, bt, bt, bt, ((batch,), jnp.float32)
    )


def test_impala_loss_grad_through_pallas_vtrace_compiles(one_chip, monkeypatch):
    """The IMPALA learner's gradient with the kernel in the loss: V-trace's
    inputs carry tangents, and the kernel must stay outside the
    linearization."""
    from repro.kernels.vtrace import ops
    from repro.rl import losses

    # the loss picks its V-trace from the backend it runs on, which is the
    # CPU here; steer it to the kernel the chip would take
    monkeypatch.setattr(
        losses, "vtrace", functools.partial(ops.vtrace, impl="pallas")
    )
    batch, T, A = 32, 20, 6

    def grad(logits, values, actions, blogp, rewards, discounts, boot):
        def total(logits, values):
            return losses.weighted_impala_loss(
                logits, values, actions, blogp, rewards, discounts, boot
            ).total

        return jax.grad(total, argnums=(0, 1))(logits, values)

    bt = ((batch, T), jnp.float32)
    _compiled_text(
        grad, one_chip, ((batch, T, A), jnp.float32), bt,
        ((batch, T), jnp.int32), bt, bt, bt, ((batch,), jnp.float32),
    )


def test_rglru_scan_compiles_for_v5e(one_chip):
    from repro.kernels.rglru_scan.rglru_scan import rglru_scan_pallas

    x = ((1, 2048, 2560), BF16)  # recurrentgemma-2b lru width
    _compiled_text(rglru_scan_pallas, one_chip, x, x, x)


def test_ssd_scan_compiles_for_v5e(one_chip):
    from repro.kernels.ssd_scan.ssd_scan import ssd_scan_pallas

    T, Hs, P, N = 2048, 64, 64, 128  # mamba2-1.3b heads, head dim, state
    _compiled_text(
        ssd_scan_pallas, one_chip,
        ((1, T, Hs, P), BF16), ((1, T, Hs), jnp.float32),
        ((Hs,), jnp.float32), ((1, T, N), BF16), ((1, T, N), BF16),
    )
