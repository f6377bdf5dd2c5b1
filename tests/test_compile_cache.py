"""Where the persistent compilation cache goes (repro/compile_cache.py):
the directory ``JAX_COMPILATION_CACHE_DIR`` names when it is set, else the
fixed ``.jax_cache/`` at the root of the checkout.  Each case runs in a
fresh interpreter, since the cache directory is process-wide JAX state.
"""
import os

from subproc import REPO_ROOT, assert_subprocess_ok

_COMPILE = """
import os
import jax, jax.numpy as jnp
from repro.compile_cache import DEFAULT_DIR, enable_compile_cache
where = enable_compile_cache()
print("WHERE", where)
print("CONFIG", jax.config.jax_compilation_cache_dir)
print("DEFAULT", DEFAULT_DIR)
"""


def test_cache_goes_where_the_environment_says(tmp_path):
    code = _COMPILE + """
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()
print("ENTRIES", len(os.listdir(where)))
print("ENV_OK")
"""
    out = assert_subprocess_ok(
        code, "ENV_OK",
        extra_env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    lines = dict(line.split(" ", 1) for line in out.splitlines()
                 if " " in line)
    assert lines["WHERE"] == str(tmp_path)
    assert lines["CONFIG"] == str(tmp_path)    # read by JAX, not set
    assert int(lines["ENTRIES"]) >= 1
    assert os.listdir(tmp_path)


def test_cache_defaults_to_fixed_path_in_checkout():
    code = ("import os\nos.environ.pop('JAX_COMPILATION_CACHE_DIR', None)\n"
            + _COMPILE + "print('DEFAULT_OK')\n")
    out = assert_subprocess_ok(code, "DEFAULT_OK")
    lines = dict(line.split(" ", 1) for line in out.splitlines()
                 if " " in line)
    want = os.path.join(REPO_ROOT, ".jax_cache")
    assert lines["WHERE"] == lines["CONFIG"] == lines["DEFAULT"] == want
