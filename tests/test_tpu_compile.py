"""The swarm round's Pallas kernels compile for a TPU v5e.

Interpret mode runs a kernel body on the CPU but never asks Mosaic, the TPU
kernel compiler, what it accepts: block shapes, layouts, memory spaces.
These tests compile each commit kernel for a described (not attached) v5e
chip at the shapes the paper-width histo round gives it — every stacked
CNN leaf after ``reshape(n, -1)``, the small BN leaves included — plus a
2^20-wide payload, and the adapter kernel at a tile-aligned projection.
Each compiled program must hold the Mosaic custom call. Nothing runs.

The topology is described inside a fixture: only one process at a time may
load the TPU library, so the call must not happen while modules import.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_histo import PAPER_FULL
from repro.kernels.fused_merge import fused_merge_tree, fused_quant_merge_tree
from repro.kernels.lora_matmul import lora_matmul
from repro.launch.hlo_stats import estimated_cycles
from repro.models.cnn import conv2d_sites, fold_sites, init_cnn, stem_sites

N = 4


@pytest.fixture(scope="module")
def topo():
    prev = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"   # no compiler logs outside
    from jax.experimental import topologies
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        if prev is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = prev


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A program compiled for a described chip can be written to the
    persistent cache but never read back here: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _stacked_payload(sharding):
    """The paper-width CNN's params stacked over N sites, plus one
    2^20-wide leaf: what the commit sees on the main path."""
    c = PAPER_FULL
    shapes = jax.eval_shape(lambda: init_cnn(
        jax.random.key(0), None, growth=c.growth, stem=c.stem,
        feat_dim=c.feat_dim, hidden=c.hidden, n_blocks=c.n_blocks,
        layers_per_block=c.layers_per_block))
    tree = {"cnn": shapes,
            "payload": jax.ShapeDtypeStruct((1 << 20,), jnp.float32)}
    return jax.tree.map(
        lambda s: _sds((N,) + s.shape, jnp.float32, sharding), tree)


def _assert_mosaic(compiled, n_calls):
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert text.count("tpu_custom_call") >= n_calls


@pytest.mark.parametrize("importance", [False, True],
                         ids=["plain", "importance"])
def test_fused_merge_all_compiles_for_v5e(one_chip, importance):
    params = _stacked_payload(one_chip)
    W = _sds((N, N), jnp.float32, one_chip)
    gates = _sds((N,), jnp.bool_, one_chip)
    imp = params if importance else None

    def commit(p, w, g, f):
        return fused_merge_tree(p, w, None, g, imp=f)

    compiled = jax.jit(commit).lower(params, W, gates, imp).compile()
    _assert_mosaic(compiled, len(jax.tree.leaves(params)))


@pytest.mark.parametrize("wire_dtype", ["int8", "bf16"])
def test_fused_quant_merge_all_compiles_for_v5e(one_chip, wire_dtype):
    params = _stacked_payload(one_chip)
    W = _sds((N, N), jnp.float32, one_chip)
    gates = _sds((N,), jnp.bool_, one_chip)

    def commit(p, ref, w, g):
        return fused_quant_merge_tree(p, ref, w, g, wire_dtype=wire_dtype,
                                      wire_block=512)

    compiled = jax.jit(commit).lower(params, params, W, gates).compile()
    _assert_mosaic(compiled, len(jax.tree.leaves(params)))


def test_lora_matmul_compiles_for_v5e(one_chip):
    m, k, n, r = 256, 2048, 2048, 16
    args = [_sds(s, jnp.bfloat16, one_chip)
            for s in ((m, k), (k, n), (k, r), (r, n))]
    scale = _sds((), jnp.float32, one_chip)
    compiled = lora_matmul.lower(*args, scale).compile()
    _assert_mosaic(compiled, 1)


def test_auto_block_tiles_fit_vmem_at_64_sites(one_chip):
    """`auto_block` sizes the tile from VMEM_BUDGET; Mosaic refuses a kernel
    whose tiles overflow the chip's scoped VMEM. At 64 sites with the
    importance stream — the largest working set — both commits compile."""
    n, d = 64, 1 << 20
    x = _sds((n, d), jnp.float32, one_chip)
    W = _sds((n, n), jnp.float32, one_chip)
    gates = _sds((n,), jnp.bool_, one_chip)

    def commits(x, w, g):
        plain = fused_merge_tree({"x": x}, w, None, g, imp={"x": x})
        quant = fused_quant_merge_tree({"x": x}, {"x": x}, w, g,
                                       imp={"x": x}, wire_dtype="int8",
                                       wire_block=512)
        return plain, quant

    _assert_mosaic(jax.jit(commits).lower(x, W, gates).compile(), 2)


def test_the_gates_stem_convolution_is_estimated_cheap_for_v5e(one_chip):
    """The gate's stem at its shape (4 sites x 38 validation rows x 224 px,
    both scorings in one loop as `SwarmEngine.sync` runs them): XLA picks a
    slow emitter for the 7x7 stride-2 folded convolution, and estimates
    the space-to-depth form's (`stem_sites`) at under a third of its
    cycles. Relative, so that it holds across compiler versions."""
    w = _sds((2, N, 7, 7, 3, PAPER_FULL.stem), jnp.float32, one_chip)
    x = _sds((N, 38, PAPER_FULL.image_size, PAPER_FULL.image_size, 3),
             jnp.float32, one_chip)

    def strided(w, x):
        return conv2d_sites(w, fold_sites(x), stride=2)

    cycles = []
    for stem in (strided, stem_sites):
        compiled = jax.jit(lambda w, x: jax.lax.map(
            lambda w: stem(w, x), w)).lower(w, x).compile()
        convs = [i for i in estimated_cycles(compiled.as_text())
                 if i["emitter"]]
        assert len(convs) == 1, convs
        cycles.append(convs[0]["cycles"])
    assert 3 * cycles[1] < cycles[0], cycles
