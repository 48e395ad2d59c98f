"""Compile the main-path kernels and the paper-CNN train step for TPU v5e.

Nothing here runs on a chip: the TPU compiler is installed with jax and
compiles for a v5e:2x2 topology that is described, not attached. That
catches what interpret mode cannot — block shapes, layouts and primitives
Mosaic refuses, and programs that overflow the chip's HBM. The topology is
described inside a fixture (never at import) and the tests skip where it
cannot be described. A compile for a described topology cannot be read
back from the persistent compilation cache, so the cache is off here.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or libtpu held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_persistent_cache):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_case(name, sharding):
    """(fn, abstract args) at granite-3-2b widths (falcon-mamba-7b for
    the scan): 32 query / 8 KV heads of 64, d_model 2048, bf16."""
    from repro.kernels import ops

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    i32 = jnp.int32
    pages = (257, 16, 8, 64)            # 8 rows x 32 pages + scratch
    if name == "flash_attention":
        return (functools.partial(ops.attention, causal=True),
                (s((1, 1024, 32, 64)), s((1, 1024, 8, 64)),
                 s((1, 1024, 8, 64))))
    if name == "paged_attention":
        return (ops.paged_attention,
                (s((8, 32, 64)), s(pages), s(pages), s((8, 32), i32),
                 s((8,), i32)))
    if name == "spec_verify":
        return (ops.spec_verify,
                (s((8, 5, 32, 64)), s(pages), s(pages), s((8, 33), i32),
                 s((8, 5), i32)))
    if name == "cross_entropy":
        # granite's 49,155-token vocab padded up to the 1024-wide block
        return (ops.cross_entropy,
                (s((1024, 2048)), s((2048, 50176)), s((1024,), i32)))
    if name == "ssm_scan":
        # falcon-mamba-7b: d_inner 8192, state 16
        return (ops.selective_scan,
                (s((1, 256, 8192)), s((1, 256, 8192)),
                 s((8192, 16), jnp.float32), s((1, 256, 16)),
                 s((1, 256, 16))))
    raise KeyError(name)


@pytest.mark.parametrize("name", ["flash_attention", "paged_attention",
                                  "spec_verify", "cross_entropy",
                                  "ssm_scan"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_case(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{name}: no Mosaic kernel in the compiled program"


def test_paper_cnn_train_step_fits_v5e(one_chip):
    """The full-width GN-ResNet-18 fused PSL step at global batch 64."""
    from repro import api
    from repro.core.psl import make_train_step
    from repro.optim import TrainState

    model = api.build_model(api.ModelSpec(arch="paper-cnn", reduced=False))
    opt = api.build_optimizer(api.OptimizerSpec(name="sgd"))

    def init():
        params = model.init(jax.random.PRNGKey(0))
        return TrainState(params, opt.init(params), jnp.zeros((), jnp.int32))

    state = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(init))
    batch = {"images": jax.ShapeDtypeStruct((64, 32, 32, 3), jnp.float32,
                                            sharding=one_chip),
             "labels": jax.ShapeDtypeStruct((64,), jnp.int32,
                                            sharding=one_chip),
             "weights": jax.ShapeDtypeStruct((64,), jnp.float32,
                                             sharding=one_chip)}
    compiled = jax.jit(make_train_step(model, opt),
                       donate_argnums=0).lower(state, batch).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES, f"{total / 1e9:.2f} GB"
