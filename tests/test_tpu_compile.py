"""Compile the Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers one kernel at the shapes the main path
gives it, compiles it with the TPU compiler for a chip that is described
and not attached, and checks that Mosaic emitted the kernel
(``tpu_custom_call``) instead of refusing it. Interpret-mode tests
cannot show this: block-shape rules, SMEM and scoped-VMEM limits are
only checked here.

Only one process at a time may load the TPU library, and it keeps the
library until it exits. So the topology is described inside a fixture
(never at import), and every such test lives in this one file, which
one test worker runs.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.codebook_lookup import codebook_lookup_pallas
from repro.kernels.dot_interaction import dot_interaction_pallas
from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.fused_topk import (fused_topk_codebook_pallas,
                                      fused_topk_pallas)

# amazonbook (configs/lightgcn_baco.py): 52,643 users x 91,599 items, d=64;
# codebook rows are of the order a quarter-ratio BACO sketch produces
N_USERS, N_ITEMS, DIM, TOPK = 52_643, 91_599, 64, 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")     # no compiler logs outside
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        mp.undo()


@pytest.fixture(scope="module")
def chip(topo):
    """One described chip, with the persistent compilation cache off (a
    compile for a described chip is written but cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _assert_kernel_compiles(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("b,h,k", [
    (N_USERS, 2, 14_000),        # amazonbook users: primary + SCU code
    (N_ITEMS, 1, 22_000),        # amazonbook items
    (200_808, 2, 40_000),        # movielens_l users: split for SMEM
])
def test_codebook_lookup_compiles(chip, b, h, k):
    _assert_kernel_compiles(
        lambda cb, idx: codebook_lookup_pallas(cb, idx, binary=True,
                                               interpret=False),
        _spec(chip, (k, DIM)), _spec(chip, (b, h), jnp.int32))


@pytest.mark.parametrize("b", [1, 512])
def test_dense_fused_scorer_compiles(chip, b):
    # the serving session's fused scorer: block 1024, capacity mask
    _assert_kernel_compiles(
        lambda u, v, m: fused_topk_pallas(u, v, TOPK, mask=m, block=1024,
                                          interpret=False),
        _spec(chip, (b, DIM)), _spec(chip, (N_ITEMS, DIM)),
        _spec(chip, (N_ITEMS,)))


def test_codebook_fused_scorer_compiles(chip):
    _assert_kernel_compiles(
        lambda u, cb, sk, m: fused_topk_codebook_pallas(
            u, cb, sk, TOPK, mask=m, block=512, interpret=False),
        _spec(chip, (512, DIM)), _spec(chip, (22_000, DIM)),
        _spec(chip, (N_ITEMS, 1), jnp.int32), _spec(chip, (N_ITEMS,)))


def test_embedding_bag_compiles(chip):
    _assert_kernel_compiles(
        lambda t, v, s: embedding_bag_pallas(t, v, s, num_segments=4096,
                                             interpret=False),
        _spec(chip, (120_000, 128)), _spec(chip, (16_384,), jnp.int32),
        _spec(chip, (16_384,), jnp.int32))


def test_flash_attention_compiles(chip):
    q = _spec(chip, (1, 8, 2048, 128), jnp.bfloat16)
    _assert_kernel_compiles(
        lambda q, k, v: flash_attention_pallas(q, k, v, interpret=False),
        q, q, q)


def test_dot_interaction_compiles(chip):
    # registry recsys serve_p99 batch; DLRM: 26 sparse + 1 dense, d=128
    _assert_kernel_compiles(
        lambda x: dot_interaction_pallas(x, block_b=128, interpret=False),
        _spec(chip, (512, 27, 128)))
