"""Where the persistent compilation cache lands. Each case runs in a
fresh process: the cache settings are process-global jax config."""
import json
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")

SCRIPT = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp
    from repro.launch import compile_cache
    path = compile_cache.enable_compile_cache()
    if {compile!r}:
        jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.ones(7)).block_until_ready()
    print(json.dumps({{"path": path,
                      "config": jax.config.jax_compilation_cache_dir,
                      "default": compile_cache.DEFAULT_DIR}}))
""")


def _run(env_dir, compile_):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    p = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(src=SRC, compile=compile_)],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_cache_lands_in_env_dir(tmp_path):
    cache = tmp_path / "xla"
    out = _run(cache, compile_=True)
    assert out["path"] == out["config"] == str(cache)
    assert any(files for _, _, files in os.walk(cache)), \
        "no cache entry written to $JAX_COMPILATION_CACHE_DIR"


def test_cache_defaults_to_checkout_dir():
    out = _run(None, compile_=False)
    assert out["path"] == out["config"] == out["default"]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert out["default"] == os.path.join(root, ".jax_cache")
