"""Every Pallas kernel × noise mode, static-k and runtime-k, compiled by the
TPU compiler (Mosaic) for a described v5e chip at the sizes
``chip_smoke.py`` campaigns at. Nothing runs: a compile that the chip's
compiler refuses fails here, at no chip time.

The topology is described inside a module fixture (never at import): only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.region import _SPECS, KERNEL_MODES

# kernel -> the spec sizes of chip_smoke.py's campaign
REAL_SIZES = {
    "matmul": {"n": 4096},
    "spmxv": {"n": 1 << 18, "nnz_per_row": 16, "q": 1.0},
    "attention": {"batch": 1, "heads": 8, "kv_heads": 1, "seq": 4096,
                  "head_dim": 256},
    "probe": {"n_steps": 64},
}
# kernel -> the name each build gives its pallas_call, which becomes the
# custom call's HLO instruction and so the operation's name in a device trace
KERNEL_NAMES = {
    "matmul": {"static": "noisy_matmul", "rt": "noisy_matmul_rt"},
    "spmxv": {"static": "spmv_ell", "rt": "spmv_ell_rt"},
    "attention": {"static": "flash_attention", "rt": "flash_attention_rt"},
    "probe": {"static": "noise_probe", "rt": "noise_probe_rt"},
}
CASES = [(kernel, mode, path) for kernel in sorted(KERNEL_MODES)
         for mode in KERNEL_MODES[kernel] for path in ("static", "rt")]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the persistent
    # cache without one; keep them out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def specs():
    """The campaign's kernel specs (argument shapes, static and runtime-k
    callables), built once per kernel for the chip (interpret=False)."""
    return {kernel: _SPECS[kernel](False, **sizes)
            for kernel, sizes in REAL_SIZES.items()}


@pytest.mark.parametrize("kernel,mode,path", CASES,
                         ids=[f"{k}-{m}-{p}" for k, m, p in CASES])
def test_kernel_compiles_for_v5e(topo, specs, kernel, mode, path):
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    spec = specs[kernel]
    shapes = [jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=one_chip)
              for a in spec.args]
    if path == "static":
        fn = spec.static_fn(mode, 5)
    else:
        fn = spec.rt_fn(mode)
        shapes = [jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
                  *shapes]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    name = KERNEL_NAMES[kernel][path]
    assert re.search(rf"%{name}(\.\d+)? = .* custom-call\(",
                     compiled.as_text()), name


# the decode cell's engine: DeepSeek-Coder-33B cut to 8 layers, 4 slots of
# 4096 positions in pages of 16
DECODE_TICK = {"layers": 8, "slots": 4, "max_seq": 4096, "page_size": 16}
V5E_HBM_BYTES = 16e9


@pytest.mark.parametrize("mode", ["hbm_stream", "fp_add32"])
def test_decode_tick_compiles_for_v5e_and_fits(topo, mode):
    """The runtime-k decode tick at published widths, as the serve kind
    wraps it, compiles for one v5e chip, and its arguments, outputs and
    temporaries fit in the chip's memory."""
    from jax.sharding import SingleDeviceSharding

    from repro.configs import get_config
    from repro.core.injector import inject_rt
    from repro.models import transformer as tf
    from repro.models.model import build
    from repro.serve.engine import _make_paged_fns
    from repro.serve.load import _registry

    t = DECODE_TICK
    cfg = get_config("deepseek-coder-33b").scaled(n_layers=t["layers"])
    one_chip = SingleDeviceSharding(topo.devices[0])
    pages = t["slots"] * t["max_seq"] // t["page_size"] + 1

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    noise = _registry([mode])[mode]
    args = on_chip((
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.eval_shape(noise.make_state, jax.random.PRNGKey(0)),
        jax.eval_shape(build(cfg).init, jax.random.PRNGKey(0)),
        jax.eval_shape(lambda: tf.lm_paged_decode_init(
            None, cfg, pages, t["page_size"])),
        jax.ShapeDtypeStruct((t["slots"], 1), jnp.int32),
        jax.ShapeDtypeStruct((t["slots"],), jnp.int32),
        jax.ShapeDtypeStruct((t["slots"],), jnp.bool_),
        jax.ShapeDtypeStruct((t["slots"], t["max_seq"] // t["page_size"]),
                             jnp.int32),
        jax.eval_shape(lambda: jax.random.PRNGKey(0))))
    tick = _make_paged_fns(cfg, 0.0)[1]
    compiled = jax.jit(inject_rt(tick, noise)).lower(*args).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 9.4e9 < mem.argument_size_in_bytes
    assert total < V5E_HBM_BYTES, total
