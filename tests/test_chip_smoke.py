"""`chip_smoke.py` rehearsed on the CPU.

The script's phases run here at a tiny size (4 sites, 24 px images, Pallas
in interpret mode); its `main` must refuse to run without a TPU, and the
script alone, outside the repo, must fail too. Neither refusal prints the
``"ok": true`` line.
"""
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, os.path.abspath(ROOT))

TINY = dict(image_size=24, growth=4, stem=8, feat_dim=32, hidden=16,
            n_blocks=1, layers_per_block=2, n_train=160, n_test=32,
            batch_size=8)


def test_one_chip_phases_pass_at_tiny_size():
    import chip_smoke as cs
    clock = cs.SetupClock()
    ecfg = cs.paper_config(rounds=2, **TINY)
    assert cs.one_chip_phases(ecfg, cs.make_shards(ecfg), clock) == [
        True, True, True]
    assert clock.seconds > 0


def test_paper_config_is_the_paper_width():
    import chip_smoke as cs
    from repro.configs.paper_histo import PAPER_FULL
    ecfg = cs.paper_config(rounds=3)
    assert (ecfg.image_size, ecfg.feat_dim, ecfg.hidden, ecfg.growth,
            ecfg.stem) == (224, 1152, 512, 32, 64) == (
        PAPER_FULL.image_size, PAPER_FULL.feat_dim, PAPER_FULL.hidden,
        PAPER_FULL.growth, PAPER_FULL.stem)
    assert ecfg.batch_size == 32 and ecfg.steps == 15
    # every site's training shard fills a batch
    shares = [round(f * ecfg.n_train) for f in ecfg.fractions]
    assert min(shares) * (1 - ecfg.val_frac) >= ecfg.batch_size


def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "chip_smoke.py", *args], cwd=cwd,
                          capture_output=True, text=True, env=env,
                          timeout=300)


@pytest.mark.parametrize("args", [[], ["--four-chips"]],
                         ids=["one_chip", "four_chips"])
def test_main_refuses_the_cpu(args):
    out = _run(args, ROOT)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_script_alone_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    out = _run([], tmp_path, {"PYTHONPATH": ""})
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


@pytest.mark.spmd
def test_four_chip_phases_pass_on_a_forced_cpu_mesh():
    """The --four-chips phase on 4 forced host devices: gossip vs engine
    backend for the paper config and ring/int8, at the tiny size."""
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {os.path.abspath(ROOT)!r})
        import chip_smoke as cs
        ecfg = cs.paper_config(rounds=2, **{TINY!r})
        mesh, _ = cs.make_swarm_mesh(4)
        ok = cs.four_chip_phases(ecfg, cs.make_shards(ecfg), mesh,
                                 cs.SetupClock())
        assert ok == [True, True], ok
        print("OK four_chip_phases")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "OK four_chip_phases" in out.stdout


def test_compile_cache_goes_where_the_variable_says(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, compiled programs land there and
    nothing points JAX elsewhere; without it, the helper names the one
    fixed directory inside the checkout. Either way source paths lose the
    checkout prefix, so another checkout of the same code hits the cache."""
    code = textwrap.dedent("""
        import os, jax, jax.numpy as jnp
        from repro.launch.compile_cache import REPO_CACHE_DIR, use_compile_cache
        placed = os.environ["JAX_COMPILATION_CACHE_DIR"]
        assert use_compile_cache() == placed
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()
        assert os.listdir(placed), "no cache entry written"
        del os.environ["JAX_COMPILATION_CACHE_DIR"]
        assert use_compile_cache() == str(REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
        assert REPO_CACHE_DIR.parent.joinpath("chip_smoke.py").is_file()
        # the checkout path is kept out of program metadata (cache keys)
        import re
        from repro.kernels import fused_merge
        regex = jax.config.jax_hlo_source_file_canonicalization_regex
        assert re.sub(regex, "", fused_merge.__file__) == os.path.join(
            "src", "repro", "kernels", "fused_merge.py")
        print("OK cache")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=os.path.join(os.path.abspath(ROOT), "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "OK cache" in out.stdout
