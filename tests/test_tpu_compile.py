"""Ahead-of-time compiles of the serving kernels for a described TPU v5e.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: blocks whose last two dims break the (8, 128) tiling,
or tiles that overflow the scoped VMEM.  These tests compile each kernel
of the serving path at OLMoE-1B-7B's published widths -- d_model 2048, 64
experts, moe_d_ff 1024, top-8, 16 x 128 KV heads, page 16, a decode batch
of 8 -- for one chip of a described ``v5e:2x2`` topology, with no chip
attached, and check that Mosaic kernels (``tpu_custom_call``) come out.

The topology is described inside a fixture (never at import), so only the
worker that runs this file loads the TPU compiler.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_decode_paged import flash_decode_paged_pallas
from repro.kernels.moe_decode import moe_decode_pallas, \
    moe_decode_quant_pallas
from repro.kernels.moe_gmm import moe_gmm_pallas, moe_gmm_quant_pallas

D, E, F, K, B = 2048, 64, 1024, 8, 8         # OLMoE-1B-7B widths
HKV, HD, PAGE = 16, 128, 16
POOL_PAGES = B * 2048 // PAGE + 1           # 8 x 2048 tokens + trash page
BLOCK_M, N_TILES = 128, 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but can never be read back here; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def test_moe_gmm_compiles(one_chip):
    _compile(lambda xs, w1, w2, te, tv: moe_gmm_pallas(
        xs, w1, w2, te, tv, block_m=BLOCK_M), one_chip,
        ((N_TILES * BLOCK_M, D), jnp.bfloat16), ((E, D, 2 * F), jnp.bfloat16),
        ((E, F, D), jnp.bfloat16), ((N_TILES,), jnp.int32),
        ((N_TILES,), jnp.int32))


def test_moe_decode_compiles(one_chip):
    _compile(moe_decode_pallas, one_chip,
             ((B, D), jnp.bfloat16), ((E, D, 2 * F), jnp.bfloat16),
             ((E, F, D), jnp.bfloat16), ((B, K), jnp.int32),
             ((B, K), jnp.float32))


def test_flash_decode_paged_compiles(one_chip):
    _compile(flash_decode_paged_pallas, one_chip,
             ((B, HKV, HD), jnp.bfloat16),
             ((POOL_PAGES, PAGE, HKV, HD), jnp.bfloat16),
             ((POOL_PAGES, PAGE, HKV, HD), jnp.bfloat16),
             ((POOL_PAGES, PAGE), jnp.int32),
             ((B, 2048 // PAGE), jnp.int32), ((B,), jnp.int32))


@pytest.mark.parametrize("dtype", ["int8", "int4"])
def test_quantized_expert_kernels_compile(one_chip, dtype):
    dp = D // 2 if dtype == "int4" else D
    tiles = (((E, dp, 2 * F), jnp.int8), ((E, F, dp), jnp.int8),
             ((E, 2, F), jnp.float32), ((E, F), jnp.float32))
    _compile(lambda x, a, b, c, d, i, w: moe_decode_quant_pallas(
        x, a, b, c, d, i, w, dtype=dtype), one_chip,
        ((B, D), jnp.bfloat16), *tiles, ((B, K), jnp.int32),
        ((B, K), jnp.float32))
    _compile(lambda xs, a, b, c, d, te, tv: moe_gmm_quant_pallas(
        xs, a, b, c, d, te, tv, dtype=dtype, block_m=BLOCK_M), one_chip,
        ((N_TILES * BLOCK_M, D), jnp.bfloat16), *tiles,
        ((N_TILES,), jnp.int32), ((N_TILES,), jnp.int32))


# --------------------------------------------------------------------------- #
# The step programs as the runner builds them
# --------------------------------------------------------------------------- #

#: what each step program must call, per expert dtype: the custom calls'
#: base names, which the benchmark's trace reduction looks up
STEP_KERNELS = {
    ("decode", "bf16"): {"moe_decode", "flash_decode_paged"},
    ("decode", "int8"): {"moe_decode_quant", "flash_decode_paged"},
    ("chunk", "bf16"): {"moe_gmm"},
    ("chunk", "int8"): {"moe_gmm_quant"},
}
_CUSTOM = re.compile(r"^\s*(?:ROOT )?%(?P<base>[A-Za-z_][A-Za-z0-9_\-]*?)"
                     r"(?:\.\d+)? = .* custom-call\(")


def _step_program(one_chip, monkeypatch, kind, dtype):
    """The runner's ``kind`` program for one OLMoE layer at published
    widths over a ``POOL_PAGES`` pool, compiled for one chip -> (HLO
    text, the pool's leaves as shapes)."""
    from repro import models
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models.moe import quantize_expert_params
    from repro.models.opts import ModelOpts
    from repro.serving.runner import ModelRunner, init_serving_params

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = get_config("olmoe-1b-7b").with_(num_layers=1)
    params = jax.eval_shape(
        lambda: init_serving_params(jax.random.PRNGKey(0), cfg))
    if dtype != "bf16":
        params = jax.eval_shape(
            lambda p: quantize_expert_params(p, cfg, dtype), params)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    opts = ModelOpts(moe_impl="gmm", use_moe_kernel=True,
                     use_paged_kernel=True, use_moe_decode_kernel=True,
                     expert_dtype=dtype)
    runner = ModelRunner(cfg, jax.tree.map(on_chip, params), opts=opts)
    caches = jax.tree.map(on_chip, jax.eval_shape(
        lambda: models.init_caches(runner.cfg_for("base"), B, 2048,
                                   layout="paged", page_size=PAGE,
                                   num_pages=POOL_PAGES)))
    bt = on_chip(jax.ShapeDtypeStruct((B, 2048 // PAGE), jnp.int32))
    if kind == "decode":
        vec = on_chip(jax.ShapeDtypeStruct((B,), jnp.int32))
        fn, args = runner._decode_call(vec, vec, caches, bt, use_kernel=True,
                                       kernel_blocks=8, moe_decode=True)
    else:
        mat = on_chip(jax.ShapeDtypeStruct((B, 128), jnp.int32))
        vec = on_chip(jax.ShapeDtypeStruct((B,), jnp.int32))
        fn, args = runner._chunk_call(mat, mat, vec, caches, bt)
    return fn.lower(*args).compile().as_text(), jax.tree.leaves(caches)


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
@pytest.mark.parametrize("kind", ["decode", "chunk"])
def test_step_programs_are_named(one_chip, monkeypatch, kind, dtype):
    """One OLMoE layer at published widths: the runner's decode and chunk
    programs compile as ``jit_decode_step`` / ``jit_chunk_step``, and
    their Mosaic custom calls carry the kernel names the benchmark's
    ``bench/trace.py`` looks for."""
    from bench.trace import KERNELS

    text, _ = _step_program(one_chip, monkeypatch, kind, dtype)
    assert text.startswith(f"HloModule jit_{kind}_step")
    mosaic = {_CUSTOM.match(line)["base"] for line in text.splitlines()
              if 'custom_call_target="tpu_custom_call"' in line}
    assert mosaic == STEP_KERNELS[(kind, dtype)]
    assert mosaic <= set(KERNELS)


_ALIAS = re.compile(r"\{[\d,]*\}: \((\d+), \{\}")
_PARAM = re.compile(r"^\s*%\S+ = \w+(\[[\d,]*\])\S* parameter\((\d+)\)",
                    re.M)
_DEF = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = (.*)$", re.M)
_COPY = re.compile(r"^\s*(?:ROOT )?%\S+ = .* copy(?:-start)?\((%[\w.\-]+)\)",
                   re.M)


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
@pytest.mark.parametrize("kind", ["decode", "chunk"])
def test_step_programs_update_the_pool_in_place(one_chip, monkeypatch, kind,
                                                dtype):
    """The decode and chunk programs take the KV pool over: every pool
    leaf (``kp``, ``vp``, ``posp``) is aliased to its output, and no copy
    reads or writes a whole ``[POOL_PAGES, 16, 16, 128]`` page leaf."""
    text, leaves = _step_program(one_chip, monkeypatch, kind, dtype)
    header = text.split("\n", 1)[0]
    aliased = {int(p) for p in _ALIAS.findall(
        header[header.index("input_output_alias="):])}
    entry = text[text.index("\nENTRY "):]
    params = {int(n): shape for shape, n in _PARAM.findall(entry)}
    pool_shapes = {"[" + ",".join(map(str, x.shape)) + "]" for x in leaves}
    pool_params = {n for n, shape in params.items() if shape in pool_shapes}
    assert len(leaves) == len(pool_params) == 3
    assert pool_params <= aliased

    page_leaf = f"[{POOL_PAGES},{PAGE},{HKV},{HD}]"
    defs = dict(_DEF.findall(text))
    for m in _COPY.finditer(text):
        line = m.group(0).split(" copy", 1)[0]      # the result's type
        assert page_leaf not in line, m.group(0)
        assert page_leaf not in defs[m.group(1)].split(" ", 1)[0], m.group(0)
