"""The entry points' persistent compilation cache placement."""
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SNIPPET = """
    import jax, jax.numpy as jnp
    from repro.launch import compile_cache
    where = compile_cache.enable()
    print(where)
    print(jax.config.jax_compilation_cache_dir)
"""


def _run(code, **env_over):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=os.path.join(ROOT, "src"), JAX_PLATFORMS="cpu",
               **env_over)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=240,
                       env=env, cwd=ROOT)
    assert r.returncode == 0, r.stderr
    return r.stdout.split()


def test_env_dir_receives_compiled_programs(tmp_path):
    cache = tmp_path / "cache"
    out = _run(_SNIPPET + """
    jax.jit(lambda x: jnp.sin(x) * 2)(jnp.arange(8.0)).block_until_ready()
    """, JAX_COMPILATION_CACHE_DIR=str(cache),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert out == [str(cache), str(cache)]
    assert any(cache.iterdir())


def test_default_dir_is_fixed_in_the_checkout():
    out = _run(_SNIPPET)
    want = os.path.join(os.path.realpath(ROOT), ".jax_cache")
    assert out == [want, want]
