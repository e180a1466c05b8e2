"""The persistent compile cache's keys do not depend on where the checkout
sits once ``enable_compile_cache`` has run, and do without it.

Two copies of one checkout's cache module and paged attention kernel, at
two paths, each run in a process of their own: a small program compiled on
the CPU into the checkout's own cache (its entry is named by its key), and
the kernel lowered for the TPU and hashed as the cache key hashes a module.
"""
import os
import shutil
import subprocess
import sys
from pathlib import Path

from repro.launch.compile_cache import source_prefix_regex

SRC = Path(__file__).resolve().parents[2] / "src" / "repro"
FILES = ("launch/compile_cache.py", "kernels/paged_attention.py")

KEYS = """
import hashlib, os, sys
import jax
import jax.numpy as jnp
from jax._src import cache_key
from repro.kernels.paged_attention import paged_decode_attention
from repro.launch import compile_cache

if sys.argv[1] == "enabled":
    path = compile_cache.enable_compile_cache()
else:  # only the cache switched on, inside the checkout, every program written
    path = str(compile_cache.ROOT / ".plain_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

jax.block_until_ready(jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)))
S = jax.ShapeDtypeStruct
bf16, i32 = jnp.bfloat16, jnp.int32
args = (S((2, 2, 3, 128), bf16), S((2, 2, 128), bf16), S((2, 2, 128), bf16),
        S((1, 6, 2, 16, 128), bf16), S((1, 6, 2, 16, 128), bf16),
        S((2, 2), i32), S((2,), i32), S((), i32))
ir = jax.jit(paged_decode_attention).trace(*args).lower(
    lowering_platforms=("tpu",)).compiler_ir("stablehlo")
kernel = hashlib.sha256(cache_key._canonicalize_ir(ir, cache_key.IgnoreCallbacks.NO)).hexdigest()
entries = sorted(n for n in os.listdir(path) if n.startswith("jit_"))
print(kernel, *entries)
"""


def _checkout(root: Path) -> Path:
    for rel in FILES:
        dst = root / "src" / "repro" / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(SRC / rel, dst)
    for pkg in ("", "launch", "kernels"):
        (root / "src" / "repro" / pkg / "__init__.py").touch()
    return root


def _keys(root: Path, mode: str) -> list[str]:
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=str(root / "src"), JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", KEYS, mode], env=env, cwd=root, capture_output=True,
        text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout.split()


def test_cache_keys_ignore_the_checkout_path(tmp_path):
    a, b = _checkout(tmp_path / "a"), _checkout(tmp_path / "elsewhere" / "b")
    plain_a, plain_b = _keys(a, "plain"), _keys(b, "plain")
    kernel_a, *entries_a = _keys(a, "enabled")
    kernel_b, *entries_b = _keys(b, "enabled")
    # without it: the cache's own path is in every key, and the kernel's
    # debug information names its source file
    assert plain_a[0] != plain_b[0]
    assert plain_a[1:] and not set(plain_a[1:]) & set(plain_b[1:])
    # with it: one kernel hash, and the same entries in both checkouts
    assert kernel_a == kernel_b != plain_a[0]
    assert entries_a and entries_a == entries_b


def test_only_the_checkout_prefix_is_stripped():
    import re

    pat = source_prefix_regex(Path("/ck/out"))
    assert re.sub(pat, "", "/ck/out/src/repro/kernels/k.py") == "src/repro/kernels/k.py"
    assert re.sub(pat, "", "/ck/outer/src/k.py") == "/ck/outer/src/k.py"
    assert re.sub(pat, "", "/venv/ck/out/k.py") == "/venv/ck/out/k.py"
