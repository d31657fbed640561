"""Ahead-of-time compiles of the main-path Pallas kernels for TPU v5e.

Nothing here runs on a chip: each test lowers a kernel with
``interpret=False`` at smollm-135m widths against a described ``v5e:2x2``
topology and compiles it with the installed TPU compiler, which refuses
what the chip would refuse (block-shape tiling, unsupported casts and
primitives, VMEM over-use).  Each asserts that the kernel survived as a
``tpu_custom_call`` in the compiled program.

The topology is described inside a module-scoped fixture and never at
import: only one process may hold the TPU library, so under several
pytest workers only the worker given this file loads it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops, wire_pack
from repro.kernels.flash_attention import (flash_attention_pallas,
                                           paged_decode_attention_pallas)
from repro.kernels.topk_compress import topk_compress_pallas

# smollm-135m attention widths
H, KH, DH = 9, 3, 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_topk_compress_r4_ef(spec):
    L = 30 * 576 * 576  # the stacked wq leaf, flattened per replica
    _assert_kernel(lambda x, t, e: topk_compress_pallas(x, t, ef=e),
                   spec((4, L), jnp.bfloat16), spec((4,), jnp.float32),
                   spec((4, L), jnp.bfloat16))


def _qkv(spec, B=8, S=2048):
    return (spec((B, S, H, DH), jnp.bfloat16),
            spec((B, S, KH, DH), jnp.bfloat16),
            spec((B, S, KH, DH), jnp.bfloat16))


@pytest.mark.parametrize("S", [2048, 200])  # 200: zero-padded to blocks
def test_flash_attention_forward(spec, S):
    _assert_kernel(lambda q, k, v: flash_attention_pallas(q, k, v),
                   *_qkv(spec, S=S))


def test_flash_attention_custom_vjp_grad(spec, monkeypatch):
    # ops routes to Pallas in interpret mode on a CPU backend; steer it to
    # the compiled kernel, as on the chip.
    monkeypatch.setattr(ops, "_interp", lambda: False)

    def loss(q, k, v):
        o = ops.flash_attention(q, k, v, causal=True, impl="pallas")
        return jnp.sum(o.astype(jnp.float32))

    # value_and_grad, as training takes it: with grad alone the forward
    # output is dead (the backward recomputes from q/k/v) and is pruned.
    _assert_kernel(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                   *_qkv(spec, S=512))


def test_flash_attention_grad_fl_cell(spec, monkeypatch):
    """value_and_grad of the Pallas route at the Qwen2.5-0.5B FL cell's
    shape: 4 clients vmapped, 2 sequences of 513 tokens, GQA 14/2 of 64;
    the dK/dV and dQ kernels lower beside the forward."""
    monkeypatch.setattr(ops, "_interp", lambda: False)
    C, B, S, Hq, Hkv = 4, 2, 513, 14, 2

    def loss(q, k, v):
        o = jax.vmap(lambda q, k, v: ops.flash_attention(
            q, k, v, causal=True, impl="pallas"))(q, k, v)
        return jnp.sum(o.astype(jnp.float32))

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        spec((C, B, S, Hq, DH), jnp.bfloat16),
        spec((C, B, S, Hkv, DH), jnp.bfloat16),
        spec((C, B, S, Hkv, DH), jnp.bfloat16)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3


def test_paged_decode_attention(spec):
    B, P, ps = 8, 16, 16
    NP = 1 + B * P
    _assert_kernel(paged_decode_attention_pallas,
                   spec((B, 1, H, DH), jnp.bfloat16),
                   spec((NP, ps, KH, DH), jnp.bfloat16),
                   spec((NP, ps, KH, DH), jnp.bfloat16),
                   spec((B, P), jnp.int32), spec((B,), jnp.int32))


@pytest.mark.parametrize("wire_dtype", ["int8", "int4", "fp8"])
@pytest.mark.parametrize("k_b", [52, 1024])  # theta_min and theta = 1
def test_encode_blocks(spec, wire_dtype, k_b):
    _assert_kernel(lambda x: wire_pack.encode_blocks_pallas(
        x, k_b, wire_dtype=wire_dtype), spec((2, 64, 1024), jnp.float32))


def test_p4_pack_unpack_offsets(spec):
    wb, k_b = 1024, 52
    lo, bm = wire_pack._p4_sizes(wb, k_b)
    _assert_kernel(lambda o: wire_pack.pack_offsets_pallas(
        o, wb=wb, mode="p4"), spec((2, 64, k_b), jnp.int32))
    _assert_kernel(lambda p: wire_pack.unpack_offsets_pallas(
        p, wb=wb, k_b=k_b, mode="p4"), spec((2, 64, lo + bm), jnp.uint8))
