"""The main-path Pallas kernels compile for a TPU v5e.

Interpret mode, which every other test runs in, cannot see the TPU's
tiling rules or its VMEM limit. The TPU compiler installed with jax
compiles for a v5e that is described rather than attached, so these
tests compile each raw kernel with ``interpret=False`` on shapes alone,
at h2o-danube-1.8b widths (d_model 2560, d_ff 6912, 8 KV heads of 80,
pages of 64 over a 4096-token window), and check that the program holds
a Mosaic kernel. Nothing runs: a pass says the chip's compiler accepts
the kernel, not that it is fast or right.

The topology is described in a fixture, never at import: only one
process at a time may load the TPU library, and pytest-xdist workers
must all collect the same tests.
"""
import functools
import inspect

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.lutq import kmeans_update_stats
from repro.kernels.kmeans_tpu import kmeans_stats
from repro.kernels.lutq_gemv_packed import lutq_gemv_packed
from repro.kernels.lutq_matmul import lutq_matmul
from repro.kernels.lutq_shift import lutq_shift
from repro.kernels.paged_attn import paged_attention_tpu, vmem_plan

D_MODEL, D_FF, LAYERS = 2560, 6912, 24
HKV, G, DH, PAGE, WINDOW = 8, 4, 80, 64, 4096
DECODE_M = 8      # a decode batch, sublane-padded


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    compile for a described device is written to it but cannot be read
    back, and the next compile would warn."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(one_chip, fn, *shapes):
    """Compile ``fn`` for the described chip; the program must carry a
    Mosaic kernel (``tpu_custom_call``)."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("m,kin,n", [
    (DECODE_M, D_MODEL, D_FF),    # MLP in, decode
    (DECODE_M, D_FF, D_MODEL),    # MLP out, decode
    (256, D_MODEL, D_FF),         # MLP in, a prefill chunk
])
def test_lutq_matmul_compiles(one_chip, m, kin, n):
    fn = functools.partial(lutq_matmul, bm=min(256, m), bn=256,
                           bk=512 if kin % 512 == 0 else 256, interpret=False)
    _compile(one_chip, fn, ((m, kin), jnp.bfloat16), ((kin, n), jnp.int8),
             ((16,), jnp.float32))


@pytest.mark.parametrize("m,kin,n", [
    (DECODE_M, D_MODEL, D_FF),    # MLP in, decode
    (DECODE_M, D_FF, D_MODEL),    # MLP out, decode
    (8 * 544, D_MODEL, D_FF),     # MLP in, 8 prompts of 544 at once
])
def test_lutq_gemv_packed_compiles(one_chip, m, kin, n):
    fn = functools.partial(lutq_gemv_packed, bm=min(256, m), bn=256,
                           bk=512 if kin % 512 == 0 else 256, interpret=False)
    _compile(one_chip, fn, ((m, kin), jnp.bfloat16),
             ((kin // 2, n), jnp.uint8), ((16,), jnp.float32))


@pytest.mark.parametrize("k", [16, 256])
def test_lutq_shift_compiles(one_chip, k):
    fn = functools.partial(lutq_shift, bm=DECODE_M, bn=256, bk=512,
                           interpret=False)
    _compile(one_chip, fn, ((DECODE_M, D_MODEL), jnp.int8),
             ((D_MODEL, D_FF), jnp.int8), ((k,), jnp.int32))


@pytest.mark.parametrize("stacked", [False, True])
def test_kmeans_stats_compiles_at_update_block(one_chip, stacked):
    """The block ``kmeans_update_stats`` uses fits v5e's scoped VMEM, on
    one MLP matrix and vmapped over the layer stack as the train step
    runs it."""
    bn = inspect.signature(kmeans_update_stats).parameters["bn"].default
    fn = functools.partial(kmeans_stats, bn=bn, interpret=False)
    n = D_MODEL * D_FF
    if stacked:
        _compile(one_chip, jax.vmap(fn), ((LAYERS, n), jnp.float32),
                 ((LAYERS, 16), jnp.float32))
    else:
        _compile(one_chip, fn, ((n,), jnp.float32), ((16,), jnp.float32))


@pytest.mark.parametrize("quant", [False, True])
@pytest.mark.parametrize("nb,twopass", [
    (9, False),                     # a 512-token prompt + 32 new tokens
    (WINDOW // PAGE + 1, True),     # the whole sliding window
])
def test_paged_attention_compiles(one_chip, quant, nb, twopass):
    kv = jnp.int8 if quant else jnp.bfloat16
    plan = vmem_plan(nb, PAGE, DH, G, quant=quant, hkv=HKV,
                     kv_itemsize=jnp.dtype(kv).itemsize, tiled=True)
    assert plan["multipass"] == twopass
    n_pages, b = 1 + 4 * nb, 4
    shapes = [((b, 1, HKV * G, DH), jnp.bfloat16),
              ((n_pages, PAGE, HKV, DH), kv), ((n_pages, PAGE, HKV, DH), kv),
              ((b, nb), jnp.int32), ((b,), jnp.int32)]
    if quant:
        shapes += [((n_pages, PAGE, HKV), jnp.bfloat16)] * 2

    def fn(q, k, v, block, cl, *scales):
        ks, vs = scales or (None, None)
        return paged_attention_tpu(q, k, v, block, cl, window=WINDOW,
                                   k_scale=ks, v_scale=vs, interpret=False)

    _compile(one_chip, fn, *shapes)
