"""Compiles for a described TPU v5e at qwen3-1.7b widths (no chip needed).

The TPU compiler is installed beside the CPU backend, so the main path's
kernels compile here for a v5e that is described, not attached: what the
chip's compiler would refuse (a block that breaks the (8, 128) tiling,
too much VMEM) fails here, at no chip time.  Nothing runs.

The topology is described inside a module fixture — never while a module
is imported — because only one process at a time may load the TPU
library; under several test workers only the worker given this file
loads it.  The persistent compile cache is off around these compiles: a
compile for a described chip is written to it but cannot be read back.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import decode_attention as dec
from repro.kernels import flash_attention as fa
from repro.kernels import rmsnorm as rn

# qwen3-1.7b (src/repro/configs/qwen3_1_7b.py) served at batch 8, page 16
B, H, KV, HD, D = 8, 16, 8, 128, 2048
PS, NMAX = 16, 36                    # 576 = 512 prompt + 64 generated
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure: no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


PAGED = [((B, H, HD), BF16), ((B * NMAX + 1, PS, KV, HD), BF16),
         ((B * NMAX + 1, PS, KV, HD), BF16), ((B, NMAX), jnp.int32),
         ((B,), jnp.int32)]


@pytest.mark.parametrize("block_t", [None, 4 * PS])
def test_paged_decode_attention_compiles(one_chip, block_t):
    _assert_kernel(_compile(
        one_chip, lambda q, k, v, bt, pos: dec.paged_decode_attention(
            q, k, v, bt, pos, block_t=block_t, interpret=False), *PAGED))


def test_paged_decode_attention_partials_compiles(one_chip):
    """The masked variant one stripe of a striped pool runs."""
    _assert_kernel(_compile(
        one_chip, lambda q, k, v, bt, pos, pm: dec.paged_decode_attention(
            q, k, v, bt, pos, page_mask=pm, partials=True,
            interpret=False), *PAGED, ((B, NMAX), jnp.int32)))


def test_decode_attention_compiles(one_chip):
    T = NMAX * PS
    _assert_kernel(_compile(
        one_chip, lambda q, k, v, pos: dec.decode_attention(
            q, k, v, pos, interpret=False),
        ((B, H, HD), BF16), ((B, T, KV, HD), BF16), ((B, T, KV, HD), BF16),
        ((), jnp.int32)))


def test_flash_attention_prefill_2048_compiles(one_chip):
    s = ((1, 2048, H, HD), BF16)
    _assert_kernel(_compile(
        one_chip, lambda q, k, v: fa.flash_attention(q, k, v,
                                                     interpret=False),
        s, s, s))


def test_rmsnorm_compiles(one_chip):
    _assert_kernel(_compile(
        one_chip, lambda x, s: rn.rmsnorm(x, s, interpret=False),
        ((B * 512, D), BF16), ((D,), jnp.float32)))


def test_decode_window_two_layers_fits(one_chip):
    """The engine's fused decode window (k=8) at full width, cut to two
    layers: it compiles for the chip and fits one chip's memory."""
    from repro import steps
    from repro.configs import get_config
    from repro.models import lm
    cfg = get_config("qwen3-1.7b").replace(n_layers=2)
    n_pages = B * NMAX + 1
    to = lambda t: jax.tree.map(                          # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    params = to(jax.eval_shape(lambda k: lm.init_params(k, cfg),
                               jax.random.PRNGKey(0)))
    pools = to(jax.eval_shape(lambda: lm.init_paged_caches(cfg, n_pages, PS)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,   # noqa: E731
                                          sharding=one_chip)
    compiled = jax.jit(steps.make_paged_serve_scan(cfg),
                       static_argnames=("k",), donate_argnums=(2,)).lower(
        params, i32(B, 1), pools, i32(B, NMAX), i32(B), i32(B),
        k=8).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < 16e9, used


# qwen3-1.7b as the serving benchmark runs it: bf16 weights, batch 16,
# page 16, requests up to 2048 tokens, the pool the exact worst case
SB, SNMAX = 16, 128


def _instructions(text):
    """(name, result shape, opcode, called computation) of every
    instruction, and each computation's root opcode."""
    inst, roots, comp = [], {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(.*\{$", line)
        if head:
            comp = head.group(1)
            continue
        m = re.match(r"\s*(ROOT )?%(\S+) = (\S+) ([\w-]+)\(", line)
        if not m:
            continue
        if m.group(1):
            roots[comp] = m.group(4)
        calls = re.search(r"calls=%([^,\s]+)", line)
        inst.append((m.group(2), m.group(3).split("{")[0], m.group(4),
                     calls and calls.group(1)))
    return inst, roots


@pytest.mark.parametrize("program",
                         ["serve_scan", "chunk_prefill", "prefill_paged"])
def test_paged_programs_update_pools_in_place(one_chip, program):
    """The decode window (k=8), the 512-token chunk prefill and the
    512-token monolithic prefill at full width, pools donated as the
    engine donates them: no instruction but the in-place scatters (a
    scatter, or a fusion whose root is one) and free views (bitcasts)
    produces a whole stacked pool or a whole layer slab of it, and the
    temporaries stay under one stacked pool.  Passing the stacks
    through the layer scan as xs/ys instead costs a slice of each
    layer's slab, a write into a fresh stack and a copy of the stack
    back, every step."""
    from repro import steps
    from repro.configs import get_config
    from repro.models import lm
    cfg = get_config("qwen3-1.7b").replace(param_dtype="bfloat16")
    n_pages = SB * SNMAX + 1
    to = lambda t: jax.tree.map(                          # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    params = to(jax.eval_shape(lambda k: lm.init_params(k, cfg),
                               jax.random.PRNGKey(0)))
    pools = to(jax.eval_shape(lambda: lm.init_paged_caches(cfg, n_pages, PS)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,   # noqa: E731
                                          sharding=one_chip)
    if program == "serve_scan":
        compiled = jax.jit(steps.make_paged_serve_scan(cfg),
                           static_argnames=("k",), donate_argnums=(2,)).lower(
            params, i32(SB, 1), pools, i32(SB, SNMAX), i32(SB), i32(SB),
            k=8).compile()
    elif program == "chunk_prefill":
        compiled = jax.jit(steps.make_chunk_prefill(cfg),
                           donate_argnums=(2,)).lower(
            params, i32(1, 512), pools, i32(SNMAX), i32(), i32()).compile()
    else:
        compiled = jax.jit(steps.make_paged_prefill_step(cfg),
                           donate_argnums=(2,)).lower(
            params, i32(1, 512), pools, i32(SNMAX)).compile()
    stack = jax.tree.leaves(pools)[0]

    def size(shape):                 # elements of a bf16 result, else 0
        m = re.fullmatch(r"bf16\[([\d,]+)\]", shape)
        return m and math.prod(map(int, m.group(1).split(",")))
    inst, roots = _instructions(compiled.as_text())
    scatter = lambda op, calls: "scatter" in (op, roots.get(calls))  # noqa
    makes = [(name, op) for name, shape, op, calls in inst
             if size(shape) in (stack.size, stack.size // stack.shape[0])
             and not scatter(op, calls)
             and op not in ("parameter", "get-tuple-element", "bitcast")]
    scatters = [name for name, shape, op, calls in inst
                if size(shape) == stack.size and scatter(op, calls)]
    assert not makes, makes
    assert len(scatters) >= 2, scatters           # k and v, in place
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < stack.size * stack.dtype.itemsize, temp


# DeepSeek-V3 as the deepseek-v3.docqa cell serves it: one chip's share
# (1 dense + 4 expert layers, 8 of 256 experts, 16,160 vocabulary rows)
# at published widths, bf16, batch 16, page 16, requests up to 32,272
# tokens, the pool the exact worst case (32,273 latent pages)
DS_NMAX = 32272 // 16


@pytest.mark.parametrize("program", ["serve_scan", "chunk_prefill"])
def test_latent_programs_fit_a_v5e_at_the_cells_size(one_chip, program):
    """The decode window (k=8, absorbed latent decode over the whole
    block row) and the 512-token chunk (the up-projected latent path,
    key block by key block) compile for one v5e with their arguments
    (weights and the 2.98 GB latent pool, donated) and temporaries under
    its 15.75 GiB, the dropless expert layer as a grouped-matmul
    kernel."""
    import dataclasses
    from repro import steps
    from repro.configs import get_config
    from repro.models import lm, mla
    full = get_config("deepseek-v3-671b")
    cfg = full.replace(n_layers=5, first_k_dense=1, vocab_size=16160,
                       mtp_depth=0, activation_dtype="bfloat16",
                       moe=dataclasses.replace(full.moe, n_held=8))
    assert not mla.absorbed_cheaper(cfg, 512) and mla.absorbed_cheaper(cfg,
                                                                       128)
    n_pages = SB * DS_NMAX + 1
    to = lambda t: jax.tree.map(                          # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    params = to(jax.eval_shape(lambda k: lm.init_params(k, cfg),
                               jax.random.PRNGKey(0)))
    pools = to(jax.eval_shape(lambda: lm.init_paged_caches(cfg, n_pages, PS)))
    assert jax.tree.leaves(pools)[1].shape == (4, n_pages, PS, 576)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,   # noqa: E731
                                          sharding=one_chip)
    if program == "serve_scan":
        compiled = jax.jit(steps.make_paged_serve_scan(cfg),
                           static_argnames=("k",), donate_argnums=(2,)).lower(
            params, i32(SB, 1), pools, i32(SB, DS_NMAX), i32(SB), i32(SB),
            k=8).compile()
    else:
        compiled = jax.jit(steps.make_chunk_prefill(cfg),
                           donate_argnums=(2,)).lower(
            params, i32(1, 512), pools, i32(DS_NMAX), i32(), i32()).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < 15.75 * 2 ** 30, used
    assert "ragged-dot" in compiled.as_text()
