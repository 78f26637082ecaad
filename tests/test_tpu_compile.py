"""v5e compile rehearsals of the main-path kernels and the smollm-135m
train step at their real widths.

Nothing runs: each program is lowered and compiled for a TPU v5e chip
that is described (``topologies.get_topology_desc``), not attached, so the
chip's compiler refuses here what interpret mode would let through
(tiling, VMEM, unsupported lowerings, memory that does not fit).  The
topology is described inside a fixture, never at import time: only one
process may load the TPU library, and the suite runs under several
workers.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.distrib.rules import rules_for
from repro.kernels.ckpt_pack.kernel import ckpt_pack
from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.rglru_scan.kernel import rglru_scan
from repro.models.api import build_model
from repro.train.optim import make_optimizer
from repro.train.schedule import warmup_cosine
from repro.train.step import make_train_step

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_ckpt_pack_compiles_for_v5e(one_chip):
    src = _sds((1024, 64, 576), jnp.bfloat16, one_chip)
    idx = _sds((1024,), jnp.int32, one_chip)
    compiled = _compile(ckpt_pack, src, idx)
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_fwd_compiles_for_v5e(one_chip):
    q = _sds((8, 2048, 9, 64), jnp.bfloat16, one_chip)
    kv = _sds((8, 2048, 3, 64), jnp.bfloat16, one_chip)
    compiled = _compile(flash_attention_fwd, q, kv, kv)
    assert "tpu_custom_call" in compiled.as_text()


def test_rglru_scan_compiles_for_v5e(one_chip):
    """recurrentgemma-9b's lru width, B > 1 (the h0 tiling case)."""
    ab = _sds((8, 2048, 4096), jnp.float32, one_chip)
    h0 = _sds((8, 4096), jnp.float32, one_chip)
    compiled = _compile(rglru_scan, ab, ab, h0)
    assert "tpu_custom_call" in compiled.as_text()


def test_smollm_135m_train_step_fits_one_v5e(topo):
    """Full-width smollm-135m AdamW step, seq 2048 x batch 8, on one chip:
    arguments plus temporaries must fit the chip's HBM."""
    cfg = get_config("smollm_135m")
    api = build_model(cfg)
    mesh = Mesh([[topo.devices[0]]], ("data", "model"),
                axis_types=(AxisType.Auto, AxisType.Auto))
    sched = functools.partial(warmup_cosine, base_lr=1e-3, warmup=2,
                              total=100)
    step = make_train_step(api, make_optimizer(cfg.optimizer), sched, mesh,
                           rules_for(cfg.arch),
                           ShapeConfig("chip", 2048, 8, "train"))
    mem = step.lower().compile().memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < V5E_HBM_BYTES, used
