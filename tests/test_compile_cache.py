"""benchmarks.common.use_compile_cache: JAX's persistent compilation cache
goes where JAX_COMPILATION_CACHE_DIR says, else to one fixed directory."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from benchmarks.common import COMPILE_CACHE, use_compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_env_dir_is_left_alone(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_unset_dir_is_the_same_fixed_path_in_two_processes():
    snippet = (
        "import sys; sys.path[:0] = [{root!r}, {src!r}]\n"
        "import jax\n"
        "from benchmarks.common import use_compile_cache\n"
        "print(use_compile_cache(), jax.config.jax_compilation_cache_dir)\n"
    ).format(root=ROOT, src=os.path.join(ROOT, "src"))
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    outs = [subprocess.run([sys.executable, "-c", snippet], env=env,
                           capture_output=True, text=True,
                           check=True).stdout.split()
            for _ in range(2)]
    assert outs[0] == outs[1] == [str(COMPILE_CACHE)] * 2
    assert COMPILE_CACHE == Path(ROOT) / ".jax_cache"
