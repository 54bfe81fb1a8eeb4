"""Launch-layer tests: dry-run machinery on a small forced-device mesh.

Runs in a subprocess because repro.launch.dryrun pins the XLA host device
count at import (the production meshes need 512 placeholder devices; tests
here use 8 to keep CPU compile fast)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=900, env=env)


def test_mesh_function_does_not_touch_devices_on_import():
    r = _run("""
        import repro.launch.mesh as m
        import jax
        # importing the module must not initialise jax devices
        assert 'jax' in dir(m)
        print("OK")
    """)
    assert r.returncode == 0, r.stderr


def test_small_mesh_train_and_decode_cells():
    """Lower+compile a train cell and a decode cell on a 2x4 mesh with the
    same build path the 512-chip dry-run uses."""
    r = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import AxisType
        from repro.common.types import ShapeSpec, MeshSpec
        from repro.configs import get_reduced
        from repro.launch import dryrun
        from repro.roofline.hlo_analysis import analyze_hlo_text

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        for arch, shape in [("phi4-mini-3.8b", ShapeSpec("t", 64, 8, "train")),
                            ("qwen2-moe-a2.7b", ShapeSpec("t", 64, 8, "train")),
                            ("phi3-mini-3.8b", ShapeSpec("d", 64, 8, "decode")),
                            ("hymba-1.5b", ShapeSpec("d", 64, 8, "decode"))]:
            cfg = get_reduced(arch)
            with mesh:
                fn, args, shards, donate = dryrun.build_cell(cfg, shape, mesh)
                compiled = jax.jit(fn, in_shardings=shards,
                                   donate_argnums=donate).lower(*args).compile()
            costs = analyze_hlo_text(compiled.as_text())
            assert costs.flops > 0, arch
            print("OK", arch, costs.flops)
    """)
    assert r.returncode == 0, r.stderr[-3000:]


def test_dryrun_artifacts_complete():
    """Every (arch x shape x mesh) cell must be present and OK/skip in the
    committed dry-run results (the deliverable-e acceptance check)."""
    d = os.path.join(REPO, "results", "dryrun")
    if not os.path.isdir(d):
        pytest.skip("dry-run sweep not yet produced")
    from repro.configs import ARCHS

    shapes = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]
    missing, failed = [], []
    for arch in ARCHS:
        for shape in shapes:
            for pod in ("singlepod", "multipod"):
                p = os.path.join(d, f"{arch}__{shape}__{pod}.json")
                if not os.path.exists(p):
                    missing.append((arch, shape, pod))
                    continue
                r = json.load(open(p))
                if not r.get("ok"):
                    failed.append((arch, shape, pod, r.get("error", "")[:80]))
    assert not missing, f"missing cells: {missing}"
    assert not failed, f"failed cells: {failed}"
