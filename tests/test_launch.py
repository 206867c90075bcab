"""Launch-layer units: HLO collective parser, roofline terms, input specs,
analytic FLOP model, sharding rules."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES_BY_NAME, get_config, smoke_variant
from repro.launch import hlo_stats


# ---------------------------------------------------------------------------
# collective-bytes parser
# ---------------------------------------------------------------------------

def test_parser_simple_ops():
    txt = """
      %ag.3 = bf16[2,1024,128]{2,1,0} all-gather(%x), dims={0}
      %ar = f32[16,4096]{1,0} all-reduce(%y), to_apply=%add
      %cp = f32[8,8]{1,0} collective-permute(%z)
      %rs = bf16[64]{0} reduce-scatter(%w)
      %a2a = f32[4,4]{1,0} all-to-all(%v)
    """
    cb = hlo_stats.collective_bytes(txt)
    assert cb["all-gather"] == 2 * 1024 * 128 * 2
    assert cb["all-reduce"] == 16 * 4096 * 4
    assert cb["collective-permute"] == 8 * 8 * 4
    assert cb["reduce-scatter"] == 64 * 2
    assert cb["all-to-all"] == 4 * 4 * 4
    assert cb["count"] == 5


def test_tile_padding_counts_unfused_operands_and_results_as_stored():
    txt = """
%fused_computation (param_0: f32[4,32]) -> f32[4,32] {
  %param_0 = f32[4,32]{1,0:T(8,128)} parameter(0)
  ROOT %neg.1 = f32[4,32]{1,0:T(8,128)} negate(%param_0)
}

ENTRY %main (x: f32[4,32]) -> f32[4,160] {
  %x = f32[4,32]{1,0:T(8,128)} parameter(0)
  %neg_fusion = f32[4,32]{1,0:T(8,128)} fusion(%x), kind=kLoop, calls=%fused_computation
  %b = f32[4,32]{1,0:T(8,128)} bitcast(%neg_fusion)
  ROOT %cat = f32[4,160]{0,1:T(8,128)} concatenate(%b, %x), dimensions={1}
}
"""
    stored, logical = hlo_stats.tile_padding(txt)
    small = 4 * 32 * 4
    # the fusion reads and writes [4, 32] stored as [8, 128]; the concat
    # reads two and writes [4, 160] with dim 0 minor: stored as [160, 128]
    assert logical == 2 * small + 2 * small + 4 * 160 * 4
    assert stored == 4 * (8 * 128 * 4) + 160 * 128 * 4


def test_estimated_cycles_reads_the_compilers_estimates_most_first():
    txt = "\n".join([
        '  %copy.3 = bf16[8,128]{1,0} copy(%p), metadata={op_name="jit(f)/'
        'cnn.stem/transpose"}, backend_config={"window_config":{'
        '"estimated_cycles":"120"}}',
        '  ROOT %fusion.2 = f32[8,128]{1,0} fusion(%copy.3, %w), kind=kOutput,'
        ' calls=%c, backend_config={"window_config":{"estimated_cycles":'
        '"4096"},"convolution_algorithm_config":{"emitter":"EmitX"}}',
        '  %add.1 = f32[8]{0} add(%a, %b), backend_config={"flag_configs":[]}',
    ])
    assert hlo_stats.estimated_cycles(txt) == [
        {"name": "fusion.2", "cycles": 4096, "op_name": "",
         "emitter": "EmitX"},
        {"name": "copy.3", "cycles": 120,
         "op_name": "jit(f)/cnn.stem/transpose", "emitter": None}]


def test_parser_tuple_result_and_async():
    txt = """
      %all-reduce = (f32[768,2304]{1,0}, f32[2304]{0}, /*index=5*/f32[10,14]{1,0}) all-reduce(%a, %b, %c)
      %ag.1 = bf16[4,128]{1,0} all-gather-start(%x)
      %agd = bf16[4,128]{1,0} all-gather-done(%ag.1)
      %trap.all-reduce.5 = f32[8]{0} add(%p, %q)
    """
    cb = hlo_stats.collective_bytes(txt)
    assert cb["all-reduce"] == (768 * 2304 + 2304 + 10 * 14) * 4
    assert cb["all-gather"] == 4 * 128 * 2   # start only, done skipped
    assert cb["count"] == 2


@pytest.mark.spmd
def test_parser_on_real_compiled_module():
    """An actual psum lowering must be visible to the parser."""
    import subprocess, sys, os, textwrap
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.hlo_stats import collective_bytes
        mesh = jax.make_mesh((4,), ("data",), devices=jax.devices()[:4])
        x = jax.ShapeDtypeStruct((16, 8), jnp.float32,
                                 sharding=NamedSharding(mesh, P("data")))
        c = jax.jit(lambda a: a.sum(0, keepdims=True) * 1.0 +
                    jax.lax.with_sharding_constraint(
                        a, NamedSharding(mesh, P())).mean()).lower(x).compile()
        cb = collective_bytes(c.as_text())
        assert cb["total"] > 0, c.as_text()
        print("OK")
    """)
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=src)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr


# ---------------------------------------------------------------------------
# roofline terms
# ---------------------------------------------------------------------------

def test_roofline_terms_and_dominant():
    r = hlo_stats.Roofline(arch="a", shape="s", mesh="m", chips=256,
                           hlo_flops=197e12, hlo_bytes=819e9,
                           coll_bytes=50e9, model_flops=100e12)
    assert r.compute_s == pytest.approx(1 / 256)
    assert r.memory_s == pytest.approx(1 / 256)
    assert r.collective_s == pytest.approx(1.0)
    assert r.dominant == "collective"
    assert r.useful_ratio == pytest.approx(100 / 197)


# ---------------------------------------------------------------------------
# analytic FLOP model
# ---------------------------------------------------------------------------

def test_model_flops_kinds():
    from repro.launch.dryrun import model_flops_analytic  # noqa: E402  (sets XLA_FLAGS; ok in-process)
    cfg = get_config("deepseek-coder-33b")
    tr = model_flops_analytic(cfg, SHAPES_BY_NAME["train_4k"])
    pf = model_flops_analytic(cfg, SHAPES_BY_NAME["prefill_32k"])
    dc = model_flops_analytic(cfg, SHAPES_BY_NAME["decode_32k"])
    n = cfg.param_count()
    assert tr == pytest.approx(6 * n * 256 * 4096)
    assert pf == pytest.approx(2 * n * 32 * 32768)
    assert dc == pytest.approx(2 * n * 128)


def test_moe_active_params_lower_than_total():
    cfg = get_config("phi3.5-moe-42b-a6.6b")
    assert cfg.active_param_count() < cfg.param_count()
    # ≈ 6.6B active vs 42B total (order of magnitude)
    assert 4e9 < cfg.active_param_count() < 10e9
    assert 35e9 < cfg.param_count() < 50e9


def test_smoke_variants_within_limits():
    for name in ("command-r-plus-104b", "phi3.5-moe-42b-a6.6b", "hymba-1.5b"):
        cfg = smoke_variant(get_config(name))
        assert cfg.n_layers <= 2 and cfg.d_model <= 512
        assert cfg.n_experts <= 4
        assert cfg.family == get_config(name).family


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

@pytest.mark.spmd
def test_param_specs_divisibility_fallback():
    import subprocess, sys, os, textwrap
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.sharding.rules import param_specs
        mesh = jax.make_mesh((2, 4), ("data", "model"), devices=jax.devices())
        tree = {"attn": {"q": {"w": jax.ShapeDtypeStruct((64, 128), jnp.float32)}},
                "mlp": {"up": {"w": jax.ShapeDtypeStruct((64, 130), jnp.float32)}}}
        specs = param_specs(tree, mesh)
        assert specs["attn"]["q"]["w"] == P("data", "model")
        # 130 % 4 != 0 -> model axis dropped on that dim
        assert specs["mlp"]["up"]["w"] == P("data", None)
        print("OK")
    """)
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=src)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr


def test_adapt_for_shape_swa():
    from repro.configs import adapt_for_shape
    cfg = get_config("deepseek-coder-33b")
    long = adapt_for_shape(cfg, SHAPES_BY_NAME["long_500k"])
    assert long.sliding_window == 4096      # dense arch gets SWA for 500k
    tr = adapt_for_shape(cfg, SHAPES_BY_NAME["train_4k"])
    assert tr.sliding_window == 0
    ssm = adapt_for_shape(get_config("mamba2-370m"), SHAPES_BY_NAME["long_500k"])
    assert ssm.sliding_window == 0          # attention-free: native path
