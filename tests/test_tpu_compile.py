"""TPU-compile tier: the main-path Pallas kernels at published widths.

Interpret mode (how every other test runs the kernels on CPU) accepts
layouts the TPU's Mosaic compiler refuses — e.g. a vector store at a
lane offset that is not a multiple of 128. These tests compile each
kernel for a *described* TPU v5e (``v5e:2x2``; no chip attached), so a
kernel the chip would refuse fails here, at no chip time:

* paged decode attention at ``moe-gpt3-s`` serving widths (Kv=8, G=1,
  D=96, page size 16, ``max_seq_len`` 512, 8 slots, bf16) and its MLA
  latent sibling at ``deepseek-v2-lite-16b`` widths;
* the grouped expert FFN at ``moe-gpt3-s`` widths (m=768, h=3072, 64
  experts) at the capacities the MoE layer computes for 8-slot decode
  and for one chip's share of an 8 x 1024-token training step.

The topology is described inside a module fixture (never at import):
only one process may load the TPU library at a time, so every xdist
worker must collect the same tests and only the worker running this
file may load it. The persistent compilation cache is off around the
compiles — an entry written for a described chip cannot be read back
without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.pipeline_moe import capacity_for
from repro.kernels.grouped_ffn import ops as gops
from repro.kernels.paged_attention import ops as pops

PAGE, MAX_SEQ, SLOTS = 16, 512, 8
NPAGES = MAX_SEQ // PAGE
POOL = SLOTS * NPAGES + 1                   # + the sink page


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else logs to /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:               # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_for_tpu(monkeypatch):
    """Kernels compiled (not interpreted) and the persistent cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(pops, "_interpret", lambda: False)
    monkeypatch.setattr(gops, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_paged_decode_compiles_at_moe_gpt3_s_widths(one_chip,
                                                    compiled_for_tpu):
    kv, g, d = 8, 1, 96
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                               sharding=one_chip)
    _compile(pops.paged_decode_attention,
             s((SLOTS, 1, kv * g, d), jnp.bfloat16),
             s((POOL, PAGE, kv, d), jnp.bfloat16),
             s((POOL, PAGE, kv, d), jnp.bfloat16),
             s((SLOTS, NPAGES), jnp.int32), s((SLOTS,), jnp.int32))


def test_paged_mla_decode_compiles_at_deepseek_v2_lite_widths(
        one_chip, compiled_for_tpu):
    h, r, e = 16, 512, 64
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                               sharding=one_chip)
    _compile(lambda qa, qr, c, k, pt, ln: pops.paged_mla_decode(
                 qa, qr, c, k, pt, ln, scale=(r + e) ** -0.5),
             s((SLOTS, 1, h, r), jnp.bfloat16),
             s((SLOTS, 1, h, e), jnp.bfloat16),
             s((POOL, PAGE, r), jnp.bfloat16),
             s((POOL, PAGE, e), jnp.bfloat16),
             s((SLOTS, NPAGES), jnp.int32), s((SLOTS,), jnp.int32))


# capacity per expert as pipeline_moe computes it:
#   decode  — 8 slots, replicated-token path (capacity factor >= 2);
#   train   — 8 x 1024 tokens over a 4-chip expert axis: 2048 local
#             tokens in n=4 chunks, each expert receiving 4 sources'
#             buffers after the dispatch All-to-All (16 experts here)
_CAPACITY = {
    "decode": (64, capacity_for(SLOTS, 1, 2.0, 64)),
    "train": (16, 4 * capacity_for(2048 // 4, 1, 1.25, 64)),
}


@pytest.mark.parametrize("case", sorted(_CAPACITY))
def test_grouped_ffn_compiles_at_moe_gpt3_s_widths(case, one_chip,
                                                   compiled_for_tpu):
    experts, cap = _CAPACITY[case]
    m, h = 768, 3072
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                           sharding=one_chip)
    _compile(lambda x, wu, wd: gops.grouped_ffn(x, wu, None, wd, "gelu"),
             s((experts, cap, m)), s((experts, m, h)), s((experts, h, m)))
