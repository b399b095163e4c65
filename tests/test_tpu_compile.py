"""Main-path programs and Pallas kernels compiled for a TPU v5e at real
widths, against a described ``v5e:2x2`` topology: nothing runs, and no
chip is needed. This is what catches kernels the TPU compiler refuses
(which interpret mode never shows), tiles that do not fit its fast
memory, and steps that do not fit the chip's 16 GB.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library, and every test
worker imports this file.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention_tpu
from repro.kernels.rglru import rglru_scan_tpu
from repro.models import steps
from repro.optim import adamw

V5E_HBM_BYTES = 16 * 10**9
# the chip smoke run's training job (chip_smoke.py TRAIN_SPEC)
TRAIN_BATCH, TRAIN_SEQ = 4, 2048
# and its serving requests: prompt 512, 32 generated tokens
PROMPT, GEN = 512, 32


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _splash_kernels(text: str) -> set:
    """Names of the splash-attention kernels a compiled program calls."""
    return set(re.findall(r"%(splash_mqa_[a-z_]+)\.\d+ = ", text))


def test_flash_attention_kernel_at_smollm_widths(one_chip):
    cfg = get_config("smollm-360m")
    q = jax.ShapeDtypeStruct((1, cfg.n_heads, TRAIN_SEQ, cfg.hd),
                             jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, cfg.n_kv_heads, TRAIN_SEQ, cfg.hd),
                              jnp.bfloat16, sharding=one_chip)
    assert _has_kernel(flash_attention_tpu.lower(q, kv, kv).compile())


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_kernel_at_recurrentgemma_width(one_chip, dtype):
    width = get_config("recurrentgemma-2b").lru_width
    assert width == 2560
    ab = jax.ShapeDtypeStruct((1, TRAIN_SEQ, width), dtype,
                              sharding=one_chip)
    h0 = jax.ShapeDtypeStruct((1, width), jnp.float32, sharding=one_chip)
    assert _has_kernel(rglru_scan_tpu.lower(ab, ab, h0).compile())


def test_smollm_train_step_fits_one_chip(one_chip):
    """The step as the platform's learner jits it (no donation): its
    attention is the splash kernel, forward (run again under remat) and
    backward, and the only loops left are the two layer scans."""
    cfg = get_config("smollm-360m")
    opt = adamw.AdamWConfig(total_steps=15)
    state = _on(one_chip, steps.abstract_train_state(cfg))
    tok = jax.ShapeDtypeStruct((TRAIN_BATCH, TRAIN_SEQ), jnp.int32,
                               sharding=one_chip)
    compiled = jax.jit(steps.make_train_step(cfg, opt)).lower(
        state, {"tokens": tok, "labels": tok}).compile()
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert need < V5E_HBM_BYTES, f"{need / 2**30:.2f} GiB"
    text = compiled.as_text()
    assert _splash_kernels(text) == {"splash_mqa_fwd_residuals",
                                     "splash_mqa_dq_no_residuals",
                                     "splash_mqa_dkv_no_residuals"}
    assert len(re.findall(r" while\(", text)) == 2


@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_smollm_serve_steps_compile(one_chip, phase):
    """The ServeEngine's prefill (1 x prompt) and decode steps."""
    cfg = get_config("smollm-360m")
    params = _on(one_chip, steps.abstract_params(cfg))
    tok = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one_chip)
    if phase == "prefill":
        fn = steps.make_prefill_step(cfg)
        args = (params, {"tokens": tok((1, PROMPT))})
    else:
        fn = steps.make_decode_step(cfg)
        states = _on(one_chip, steps.abstract_decode_state(cfg, 1,
                                                           PROMPT + GEN))
        args = (params, tok((1, 1)), states, tok(()))
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < V5E_HBM_BYTES
    if phase == "prefill":  # the kernel's forward over the prompt
        assert _splash_kernels(compiled.as_text()) == {
            "splash_mqa_fwd_no_residuals"}
