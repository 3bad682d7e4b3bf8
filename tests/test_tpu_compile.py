"""Real-size compiles for a TPU v5e that is described, not attached.

The TPU compiler is installed with JAX, so the routers' chip programs can be
compiled for a described v5e:2x2 topology on a machine without a chip: this
catches what interpret mode cannot (block shapes that break the TPU tiling,
primitives Mosaic cannot lower, programs that do not fit) at no chip time.
Nothing runs, so these tests say nothing about results or speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.  The persistent compilation cache is off while these
tests run: an entry written for a described chip cannot be read back here.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from repro.core.estimation import OnlineSS
from repro.kernels import platform
from repro.kernels.moe_pkg_dispatch import moe_adaptive_dispatch, moe_pkg_dispatch
from repro.kernels.ss_update import ss_update_chunk
from repro.parallel.chunked_driver import ChunkedRouter, clear_step_cache
from repro.parallel.sharded_router import routed_step_roofline, sharded_route
from repro.roofline.analysis import V5E

W = 100  # the paper's largest worker count
CHUNK = 8192  # ChunkedRouter's default chunk
N = 262_144  # the sharded router's one-chip stream: 2,048 vector blocks
H = 256  # Space-Saving capacity = head-table width
BLOCK = 128
CAPS = np.array([1.0 + (i % 4) for i in range(W)], np.float32)  # 1-4 pattern


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _compile_chunk_step(chip, policy, d_max, capacities=None):
    """ChunkedRouter's chunk step at the real chunk, for the described chip."""
    router = ChunkedRouter(W, policy, chunk=CHUNK, block=BLOCK, d_max=d_max,
                           capacities=capacities)
    carry = jax.tree.map(
        lambda a: _spec(chip, a.shape, a.dtype), router._carry
    )
    seeds = _spec(chip, router._seeds.shape, router._seeds.dtype)
    chunk = _spec(chip, (CHUNK,), jnp.int32)
    icap = None if capacities is None else _spec(chip, (1, W), jnp.float32)
    return router._step.lower(carry, chunk, chunk, seeds, icap).compile()


@pytest.fixture
def tpu_kernels(monkeypatch):
    """Kernels called with `interpret=None` compile for the TPU during the
    test: the CPU backend otherwise resolves them to interpret mode.  Traces
    made either way are dropped around it."""
    monkeypatch.setattr(platform, "interpret_default", lambda: False)
    clear_step_cache()
    jax.clear_caches()
    yield
    clear_step_cache()
    jax.clear_caches()


@pytest.mark.parametrize(
    "policy,d_max",
    [("pkg", 8), ("d_choices", 8), ("w_choices", 8), ("d_choices", W)],
    ids=["pkg", "d_choices", "w_choices", "d_choices-d_max100"],
)
def test_chunk_step_compiles(chip, tpu_kernels, policy, d_max):
    """ChunkedRouter's chunk step, the main path chip_smoke.py runs: an XLA
    program, which for the adaptive policies holds the Space-Saving
    kernel.  D-Choices also at d_max = W, the benchmark's wp_dchoices_w100:
    a (128, 100) candidate table and a (12,800 x 100) one-hot fetch per
    block."""
    compiled = _compile_chunk_step(chip, policy, d_max)
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20
    assert ("tpu_custom_call" in compiled.as_text()) == (policy != "pkg")


@pytest.mark.parametrize("decay_period", [0, 1000])
def test_ss_update_chunk_compiles(chip, decay_period):
    """One chunk's Space-Saving pass, the summary held on chip."""
    state = OnlineSS(*(_spec(chip, s, jnp.int32) for s in [(H,)] * 3 + [()]))
    rows = _spec(chip, (CHUNK // BLOCK, BLOCK), jnp.int32)
    compiled = _compile(
        lambda s, k, v, b: ss_update_chunk(
            s, k, v, b, decay_period=decay_period, interpret=False
        ),
        state, rows, rows, _spec(chip, (), jnp.int32),
    )
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_pkg_route_compiles(chip, tpu_kernels):
    """The PKG chunk step with capacities: the capacity-normalized
    candidate fetch, no Space-Saving kernel."""
    compiled = _compile_chunk_step(chip, "pkg", 2, CAPS)
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20
    assert "tpu_custom_call" not in compiled.as_text()


@pytest.mark.parametrize("w_mode,d_max", [(False, 8), (True, 2)])
def test_adaptive_route_online_compiles(chip, tpu_kernels, w_mode, d_max):
    """The D- and W-Choices chunk steps with capacities.  For W-Choices,
    capacities select waterfill_assign (one argmin per head lane) inside
    the XLA scan, which no other v5e compile covers."""
    policy = "w_choices" if w_mode else "d_choices"
    compiled = _compile_chunk_step(chip, policy, d_max, CAPS)
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20
    assert "tpu_custom_call" in compiled.as_text()


def test_w_route_compiles_with_capacities(topo, chip):
    """sharded_route on one chip with per-key candidate counts under
    w_mode and capacities: the one-shard W-Choices path the device-backed
    routing policies run."""
    mesh = Mesh(
        np.asarray(topo.devices[:1]), ("data",), axis_types=(AxisType.Auto,)
    )
    compiled = _compile(
        lambda k, nc, c: sharded_route(
            k, nc, W, d_max=2, block=BLOCK, w_mode=True, mesh=mesh,
            capacities=c,
        ),
        _spec(chip, (N,), jnp.int32), _spec(chip, (N,), jnp.int32),
        _spec(chip, (W,), jnp.float32),
    )
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


# router widths: Mixtral-8x7B routes each token to k=2 of E=8 experts,
# OLMoE-1B-7B to k=8 of E=64; T is one 8k-token sequence
MOE_WIDTHS = [pytest.param(8, 2, id="mixtral"), pytest.param(64, 8, id="olmoe")]
T = 8192


@pytest.mark.parametrize("E,k", MOE_WIDTHS)
def test_moe_pkg_dispatch_compiles(chip, E, k):
    compiled = _compile(
        lambda c, g: moe_pkg_dispatch(c, g, E, block=256, interpret=False),
        _spec(chip, (T, k, 2), jnp.int32), _spec(chip, (T, k, 2), jnp.float32),
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("w_mode,d_max", [(False, 4), (True, 2)])
@pytest.mark.parametrize("E,k", MOE_WIDTHS)
def test_moe_adaptive_dispatch_compiles(chip, E, k, w_mode, d_max):
    tbl = _spec(chip, (T // 256, E), jnp.int32)
    compiled = _compile(
        lambda c, g, tk, tn: moe_adaptive_dispatch(
            c, g, tk, tn, E, d_max=d_max, block=256, interpret=False,
            w_mode=w_mode,
        ),
        _spec(chip, (T, k, d_max), jnp.int32),
        _spec(chip, (T, k, d_max), jnp.float32), tbl, tbl,
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("sync_period", [1, 16])
def test_sharded_route_compiles_for_four_chips(topo, sync_period):
    """The 4-shard load-sync program over the described 2x2 chips: one
    all-reduce of the f32 loads row (4*W bytes) per epoch, and v5e peaks
    picked by the devices' own kind."""
    mesh = Mesh(
        np.asarray(topo.devices), ("data",), axis_types=(AxisType.Auto,)
    )
    assert mesh.devices.flat[0].device_kind == V5E
    rep = routed_step_roofline(
        W, n_shards=4, sync_period=sync_period, n_epochs=4, block=BLOCK,
        d_max=2, w_mode=True, mesh=mesh,
    )
    assert rep["collective_counts"]["all-reduce"] == 1
    assert rep["collective_bytes_per_epoch"] == 4 * W
    assert rep["roofline"]["step_lower_bound_s"] > 0
