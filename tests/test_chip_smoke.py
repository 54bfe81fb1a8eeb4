"""``chip_smoke.py`` without a chip: it refuses to run on the CPU, and its
phases (device run vs the scalar full-copy reference) pass at a tiny size
with the Pallas kernels in interpret mode."""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def test_chip_smoke_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          capture_output=True, text=True, env=env,
                          timeout=120, cwd=ROOT)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


@pytest.mark.parametrize("tls,policy", [(None, True), ("hw", False)])
def test_chip_smoke_phase_passes_in_interpret_mode(tls, policy):
    rng = np.random.default_rng(0)
    traffic = chip_smoke.make_traffic(rng, n_conns=6, n_msgs=2,
                                      header=(100, 140), bodies=(300, 600))
    line = chip_smoke.check_phase(
        "tiny", traffic, impl="fused-round:interpret", tls=tls,
        policy=policy, page=128, pool_pages=6 * 5)
    assert line["messages"] == 12
    assert line["fused_rounds"] > 0 and line["device_fallbacks"] == 0


def test_chip_smoke_phase_catches_a_wrong_byte(monkeypatch):
    """The phase check compares bytes, not just counts: one flipped token
    in a backend's received stream fails it."""
    real = chip_smoke.run_proxy

    def corrupt(*a, **kw):
        res = real(*a, **kw)
        if kw["batched"]:
            res["wires"][0] = res["wires"][0].copy()
            res["wires"][0][-1] ^= 1
        return res

    monkeypatch.setattr(chip_smoke, "run_proxy", corrupt)
    traffic = chip_smoke.make_traffic(np.random.default_rng(1), n_conns=2,
                                      n_msgs=1, header=(100, 140),
                                      bodies=(300,))
    with pytest.raises(AssertionError, match="different bytes"):
        chip_smoke.check_phase("tiny", traffic, impl="fused-round:ref",
                               tls=None, policy=False, page=128,
                               pool_pages=2 * 3)


def test_compile_cache_follows_env_var_else_fixed_checkout_path(
        monkeypatch, tmp_path):
    import jax

    from repro.common.compile_cache import ENV_VAR, use_compile_cache

    opts = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_enable_xla_caches",
            "jax_hlo_source_file_canonicalization_regex")
    prev = {k: getattr(jax.config, k) for k in opts}
    try:
        monkeypatch.setenv(ENV_VAR, str(tmp_path / "from-env"))
        assert use_compile_cache(ROOT) == str(tmp_path / "from-env")
        # no directory set in code
        assert jax.config.jax_compilation_cache_dir == \
            prev["jax_compilation_cache_dir"]
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        monkeypatch.delenv(ENV_VAR)
        want = str(ROOT.resolve() / ".jax_cache")
        assert use_compile_cache(ROOT) == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        for k, v in prev.items():
            jax.config.update(k, v)


_CACHE_PROBE = """
import sys
import jax, jax.numpy as jnp
from jax import monitoring
from repro.common.compile_cache import use_compile_cache
hits = []
monitoring.register_event_listener(
    lambda event, **_: hits.append(event)
    if event == "/jax/compilation_cache/cache_hits" else None)
use_compile_cache(sys.argv[1])
jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()
print(len(hits))
"""


def test_compile_cache_is_found_again_from_a_copied_checkout(tmp_path):
    """A run from a copy of the checkout (cache included) at another path
    reads back what the first run compiled: short compiles are written,
    and the cache key does not hash the cache's own path."""
    import shutil

    from repro.common.compile_cache import ENV_VAR

    env = {k: v for k, v in os.environ.items() if k != ENV_VAR}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))

    def hits(root):
        proc = subprocess.run([sys.executable, "-c", _CACHE_PROBE, str(root)],
                              capture_output=True, text=True, env=env,
                              timeout=120, cwd=root)
        assert proc.returncode == 0, proc.stderr
        return int(proc.stdout.split()[-1])

    first = tmp_path / "checkout"
    first.mkdir()
    assert hits(first) == 0
    shutil.copytree(first, tmp_path / "copy")
    assert hits(tmp_path / "copy") >= 1
