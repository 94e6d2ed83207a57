"""Ahead-of-time compiles for a described TPU v5e: the kernels and steps of
the main paths at their real widths, through the chip's own compiler
(Mosaic for Pallas, XLA:TPU for the steps).  Nothing runs: these catch what
interpret mode cannot — block shapes the tiling rules refuse, more VMEM than
a kernel may use, a step program that does not fit 16 GB of HBM.

The topology is described inside a module fixture, never while a module is
imported: only one process at a time may load the TPU library, and the test
workers must all collect the same tests.  Keep these tests in this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 10**9

# stablelm-1.6b serving geometry: max_batch 8, 32 KV heads of 64, page 16,
# max_seq 544 (512-token prompts + 32 new tokens)
B, HK, G, D, PAGE, NPP = 8, 32, 1, 64, 16, 34
# the chat benchmark cell's: 16 slots, max_seq 1024, 577 pages
CHAT = dict(b=16, npp=64, n_pages=577)
SERVE = dict(b=B, npp=NPP, n_pages=1 + B * NPP)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check against
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip can be written to the persistent cache
    but not read back without the chip; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


@pytest.mark.parametrize("pages_per_program,geom", [
    pytest.param(p, SERVE, id=str(p)) for p in (1, 4, 8)] + [
    pytest.param(p, CHAT, id=f"chat-{p}") for p in (4, 8, 16)])
def test_paged_decode_kernel_compiles_at_stablelm_shapes(
        one_chip, no_persistent_cache, pages_per_program, geom):
    from repro.kernels.flash_decode.kernel import paged_flash_decode_pallas

    b, npp = geom["b"], geom["npp"]
    pool = _spec(one_chip, (geom["n_pages"], HK, PAGE, D), jnp.bfloat16)
    fn = jax.jit(lambda q, k, v, lens, pt: paged_flash_decode_pallas(
        q, k, v, lens, pt, pages_per_program=pages_per_program,
        interpret=False))
    compiled = fn.lower(
        _spec(one_chip, (b, HK, G, D), jnp.bfloat16), pool, pool,
        _spec(one_chip, (b,), jnp.int32), _spec(one_chip, (b, npp), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sdca_kernel_compiles_at_cocoa_shard(one_chip, no_persistent_cache):
    """The paper's CoCoA MNIST problem (n=60000, d=784) on m=16 workers:
    each worker's whole shard stays in VMEM for its H=nl local steps."""
    from repro.kernels.sdca.kernel import VMEM_CAPACITY, local_sdca_pallas, vmem_bytes

    m, nl, d = 16, 60000 // 16, 784
    assert vmem_bytes(nl, d) <= VMEM_CAPACITY
    fn = jax.jit(lambda X, y, a, w, idx: local_sdca_pallas(
        X, y, a, w, idx, 1.0, 1e-4, float(m * nl), interpret=False))
    f32 = jnp.float32
    compiled = fn.lower(
        _spec(one_chip, (m, nl, d), f32), _spec(one_chip, (m, nl), f32),
        _spec(one_chip, (m, nl), f32), _spec(one_chip, (d,), f32),
        _spec(one_chip, (m, nl), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("step,geom", [
    pytest.param("decode", SERVE, id="decode"),
    pytest.param("prefill_chunk", SERVE, id="prefill_chunk"),
    pytest.param("decode", CHAT, id="decode-chat")])
def test_stablelm_serve_step_fits_one_v5e(one_chip, no_persistent_cache,
                                          monkeypatch, step, geom):
    """One whole 24-layer serve step at the engine's geometry compiles for
    one chip, with the paged Pallas kernel native (the backend here is the
    CPU, so the test itself turns interpret mode off), and its arguments,
    outputs and temporaries fit the chip's HBM."""
    import repro.kernels.flash_decode.kernel as paged_kernel
    from repro.configs import get_config
    from repro.models.model import LM
    from repro.serve import ServeEngine
    from repro.serve.cache import init_paged_cache

    monkeypatch.setattr(paged_kernel, "pallas_interpret", lambda interpret=None: False)
    lm = LM(get_config("stablelm-1.6b"),
            ServeEngine.default_runtime(PAGE, paged_impl="pallas"))
    place = lambda tree: jax.tree.map(
        lambda s: _spec(one_chip, s.shape, s.dtype), tree)
    params = place(lm.param_shapes())
    b, npp = geom["b"], geom["npp"]
    cache = place(jax.eval_shape(lambda: init_paged_cache(
        lm, num_pages=geom["n_pages"], page_size=PAGE, max_batch=b)))
    i32 = lambda *shape: _spec(one_chip, shape, jnp.int32)
    if step == "decode":
        lowered = jax.jit(lm.decode_step_paged, donate_argnums=(3,)).lower(
            params, i32(b), i32(b), cache, i32(b, npp))
    else:
        lowered = jax.jit(lm.prefill_chunk, static_argnames=("s0",),
                          donate_argnums=(3,)).lower(
            params, i32(1, 256), i32(), cache, i32(1, npp), s0=256)
    compiled = lowered.compile()
    assert ("tpu_custom_call" in compiled.as_text()) == (step == "decode")
    assert _device_bytes(compiled) < V5E_HBM_BYTES
