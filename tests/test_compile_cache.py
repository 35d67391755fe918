"""The persistent compilation cache: the environment wins, and otherwise a
fixed path inside the checkout.  Each case runs in a child process so the
test workers' own JAX configuration stays untouched."""

import os

from tests._subproc import REPO, run_with_devices

CODE = r"""
import os
{env}
import jax
from repro.launch.compile_cache import enable_compile_cache
used = enable_compile_cache()
print(used)
print(jax.config.jax_compilation_cache_dir)
"""


def test_env_var_wins(tmp_path):
    want = str(tmp_path / "cache")
    out = run_with_devices(CODE.format(env=f"os.environ['JAX_COMPILATION_CACHE_DIR'] = {want!r}"), 1)
    assert out.split() == [want, want]


def test_default_is_fixed_checkout_path():
    out = run_with_devices(CODE.format(env="os.environ.pop('JAX_COMPILATION_CACHE_DIR', None)"), 1)
    want = os.path.join(REPO, ".jax_cache")
    assert out.split() == [want, want]
