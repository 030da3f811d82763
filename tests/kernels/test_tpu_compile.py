"""The device programs compile for a TPU v5e at the paper's cluster scale.

Compiled for a described (not attached) ``v5e:2x2`` chip: the TPU compiler
refuses what interpret mode accepts (float iotas, blocks off the (8, 128)
tiling).  The topology is described inside a fixture, so collection never
loads the TPU library, and the persistent compilation cache stays off:
an entry compiled here could not be read back without the chip.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

N_HOSTS = 12_600        # the paper's cluster (§VII-C)
BATCH = 256             # pending VMs scored in one batched call
N_TICKS, N_PATHS, N_POOLS = 1_440, 1_024, 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _scoring_args(one_chip, batch):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    masks = s((batch, N_HOSTS), jnp.bool_) if batch else s((N_HOSTS,),
                                                            jnp.bool_)
    alphas = s((batch,), jnp.float32) if batch else s((), jnp.float32)
    return (s((N_HOSTS, 4), jnp.float32), masks,
            s((N_HOSTS, 4), jnp.float32), alphas)


def _compiled_text(fn, *args) -> str:
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


@pytest.mark.parametrize("kernel,batch", [("hlem_score_pallas", 0),
                                          ("hlem_score_pallas_batch", BATCH)])
def test_pallas_kernel_compiles_for_v5e(one_chip, kernel, batch):
    from repro.kernels import hlem_score
    fn = getattr(hlem_score, kernel)
    text = _compiled_text(fn, *_scoring_args(one_chip, batch))
    assert "tpu_custom_call" in text


def test_hlem_select_jax_compiles_for_v5e(one_chip):
    from repro.core.hlem import hlem_select_jax
    assert _compiled_text(hlem_select_jax, *_scoring_args(one_chip, 0))


def test_float64_auction_scan_compiles_for_v5e(one_chip):
    from repro.market.price_process import AUCTION_FAMILY, price_scan
    state = AUCTION_FAMILY.init([{"seed": i} for i in range(N_POOLS)])
    with jax.enable_x64(True):
        args = (
            {k: jax.ShapeDtypeStruct(np.shape(v), jnp.float64,
                                     sharding=one_chip)
             for k, v in state.items()},
            jax.ShapeDtypeStruct((N_TICKS, N_POOLS), jnp.float64,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((N_TICKS, N_PATHS, N_POOLS), jnp.float64,
                                 sharding=one_chip))
        text = _compiled_text(functools.partial(price_scan, AUCTION_FAMILY),
                              *args)
    assert "f64" in text


@pytest.mark.parametrize("rows", [256, 16_384])
def test_resident_pick_compiles_for_v5e_and_updates_in_place(one_chip, rows):
    """The device pick at the market day's storage and at the paper
    cluster's (12,600 hosts in 16,384 rows): its mirror is donated, so the
    changed rows are written into it in place."""
    from repro.core.hlem import hlem_scores_tol_jax_resident, pack_pick
    packed = pack_pick(np.zeros(rows, dtype=bool), 0.0, [],
                       np.zeros((rows, 4)), np.zeros((rows, 4)))
    mirror = jax.ShapeDtypeStruct((rows, 4), jnp.float32, sharding=one_chip)
    args = (mirror, mirror, jax.ShapeDtypeStruct(packed.shape, packed.dtype,
                                                 sharding=one_chip))
    compiled = hlem_scores_tol_jax_resident.lower(*args).compile()
    assert "input_output_alias" in compiled.as_text()


@pytest.mark.parametrize("rows", [256, 16_384])
def test_run_pick_compiles_for_v5e_and_updates_in_place(one_chip, rows):
    """A run of ``PICK_RUN_K`` placements in one call, at both storages:
    the mirror is donated and written in place; ``tot`` is only read."""
    from repro.core.hlem import (PICK_RUN_K, hlem_scores_tol_jax_run,
                                 pack_run)
    packed = pack_run(np.zeros((1, rows), dtype=np.uint8), np.zeros((1, 10)),
                      0.95, 0.0, PICK_RUN_K, [], np.zeros((rows, 4)),
                      np.zeros((rows, 4)))
    mirror = jax.ShapeDtypeStruct((rows, 4), jnp.float32, sharding=one_chip)
    args = (mirror, mirror, mirror,
            jax.ShapeDtypeStruct(packed.shape, packed.dtype,
                                 sharding=one_chip))
    compiled = hlem_scores_tol_jax_run.lower(*args).compile()
    assert "input_output_alias" in compiled.as_text()
