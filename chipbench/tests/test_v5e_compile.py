"""The cells' programs at their real sizes, compiled for a described TPU v5e
chip (no chip needed): the SPMXV kernel at the campaign cell's rows."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench.harness import BENCH_DIR


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _config(name):
    return json.loads((BENCH_DIR / "configs" / name).read_text())


def test_spmxv_kernel_at_the_cells_rows(one_chip):
    from repro.kernels.spmv_ell.kernel import spmv_ell_pallas_rt

    cfg = _config("spmxv-ell-band16.json")
    n, nnz = cfg["rows"], cfg["nnz_per_row"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    for mode in ("fp", "vmem"):
        fn = jax.jit(lambda k, v, c, x, mode=mode: spmv_ell_pallas_rt(
            k, v, c, x, br=cfg["block_rows"], mode=mode))
        hlo = fn.lower(sds((), jnp.int32), sds((n, nnz), jnp.float32),
                       sds((n, nnz), jnp.int32), sds((n,), jnp.float32)
                       ).compile().as_text()
        assert "tpu_custom_call" in hlo

