"""The proxy datapath's Pallas kernels compile for a TPU v5e chip.

Compiled for a described (not attached) ``v5e:2x2`` chip at the shapes
``chip_smoke.py`` runs: 256 messages, 4096-token pages, 16 pages per
message, a 384-lane metadata region and a 4097-row resident pool. The TPU
compiler refuses what interpret mode accepts (unaligned lane slices,
blocks that do not tile), so these catch layout regressions without a
chip. The topology is described inside a fixture only: describing it
loads the TPU library, which one process at a time may hold.

The per-message operands are passed in the [N, 1, W] row view the host
builds (``LibraStack._round_operands``, ``DevicePool``), so the tests can
pin their device layout: tiled (1, 128), i.e. unpadded in HBM, and
consumed by the kernel with no relayout copy.
"""
import base64
import re
from pathlib import Path

import pytest

B, PAGE, PPS, META_MAX = 256, 4096, 16, 384
POOL_ROWS = B * PPS + 1          # one full round in flight + scratch row
S = META_MAX + PPS * PAGE
R, K = 2, 1                      # the smoke's two-backend routing table
ROW_TILING = "{2,1,0:T(1,128)}"  # a row view's HBM layout: no sublane padding


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """ShapeDtypeStruct factory on one described chip, with the persistent
    compilation cache off (such compiles cannot be read back without a
    chip)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda *dims: jax.ShapeDtypeStruct(dims, jnp.int32,
                                             sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", prev)


def _compiled_text(fn, *args, **kw) -> str:
    return fn.lower(*args, **kw).compile().as_text()


def _param_layout(text: str, name: str) -> str:
    """Compiled shape and layout of entry parameter ``name``."""
    m = re.search(rf"%{name}(?:\.\d+)? = (\S+) parameter\(", text)
    assert m, f"no entry parameter {name}"
    return m.group(1)


@pytest.mark.parametrize("variant,n_buffers", [
    ("plain", 0), ("hw_ktls_policy_live", 0), ("hw_ktls_policy_live", 2)])
def test_fused_round_compiles_for_v5e(shape, variant, n_buffers):
    """The one-kernel round against the resident row-view pool, as
    ``DevicePool.fused_round_device`` dispatches it (pool donated), in the
    blocked and the DMA-staged layout. The pool and the per-round streams
    keep the unpadded (1, 128) row tiling and reach the kernel with no
    relayout: the round needs no temporary of a stream's or the pool's
    size, and its arguments take no more HBM than their elements."""
    from repro.kernels.selective_copy import fused_round_donated

    rows = {"stream": (B, 1, S), "pool": (POOL_ROWS, 1, PAGE)}
    if variant != "plain":
        rows.update(keystream=(B, 1, S), tx_keystream=(B, 1, PPS * PAGE))
    args = [shape(*rows["stream"]), shape(B), shape(B),
            shape(*rows["pool"]), shape(B, PPS)]
    if variant != "plain":
        args += [shape(*rows["keystream"]), shape(*rows["tx_keystream"]),
                 shape(R, K), shape(R, K), shape(R, K), shape(R),
                 shape(B, META_MAX)]
    compiled = fused_round_donated.lower(
        *args, meta_max=META_MAX, n_buffers=n_buffers).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    for name, dims in rows.items():
        shp = "s32[" + ",".join(map(str, dims)) + "]"
        assert _param_layout(text, name) == shp + ROW_TILING, name
        assert not re.search(rf" (copy|reshape|fusion)\(%{name}\b", text), name
    mem = compiled.memory_analysis()
    elems = sum(a * b * c for a, b, c in rows.values())
    assert mem.argument_size_in_bytes <= 4 * elems + (1 << 20)
    assert mem.temp_size_in_bytes < 4 * B * S


def test_selective_copy_compiles_for_v5e(shape):
    from repro.kernels.selective_copy import selective_copy_donated

    text = _compiled_text(
        selective_copy_donated, shape(B, 1, S), shape(B), shape(B),
        shape(POOL_ROWS, 1, PAGE), shape(B, PPS), meta_max=META_MAX,
        reserved_scratch=True, keystream=None)
    assert "tpu_custom_call" in text
    assert _param_layout(text, "stream") == f"s32[{B},1,{S}]" + ROW_TILING


def test_selective_gather_compiles_for_v5e(shape):
    from repro.kernels.selective_copy import selective_gather

    text = _compiled_text(selective_gather, shape(POOL_ROWS, 1, PAGE),
                          shape(B, PPS), shape(B))
    assert "tpu_custom_call" in text


def test_policy_match_compiles_for_v5e(shape):
    """The multi-pass round's standalone match: hw-kTLS metadata
    keystream, live column and payload-prefix window all on."""
    import jax

    from repro.kernels.selective_copy import policy_match

    def match(meta, meta_len, off, lo, hi, ks, live, window, plen):
        return policy_match(meta, meta_len, off, lo, hi, keystream=ks,
                            live=live, payload=window, payload_len=plen)

    m = 300                      # unpadded metadata width of a round
    text = _compiled_text(jax.jit(match), shape(B, m), shape(B),
                          shape(R, K), shape(R, K), shape(R, K), shape(B, m),
                          shape(R), shape(B, PAGE), shape(B))
    assert "tpu_custom_call" in text


def _kernel_body(text: str) -> bytes:
    """The serialized kernel of the first TPU custom call in ``text``."""
    cfg = re.search(r'backend_config = "(.*?)"', text).group(1)
    body = re.search(r"\\22body\\22: \\22([A-Za-z0-9+/=]+)", cfg).group(1)
    return base64.b64decode(body)


def test_kernel_cache_key_holds_no_checkout_path(shape):
    """A compiled kernel's body is part of its persistent-cache key and
    records its source file. After ``use_compile_cache`` that file is
    named relative to the checkout, so a run from a copy of the checkout
    at another path finds the entries an earlier run wrote."""
    import jax

    from repro.common.compile_cache import use_compile_cache
    from repro.kernels.selective_copy import selective_gather

    root = Path(__file__).resolve().parents[1]
    args = (shape(9, 1, 128), shape(4, 2), shape(4))
    opts = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_enable_xla_caches",
            "jax_hlo_source_file_canonicalization_regex")
    prev = {k: getattr(jax.config, k) for k in opts}
    try:
        jax.clear_caches()
        before = _kernel_body(selective_gather.lower(*args).as_text())
        use_compile_cache(root)
        jax.clear_caches()
        after = _kernel_body(selective_gather.lower(*args).as_text())
    finally:
        for k, v in prev.items():
            jax.config.update(k, v)
        jax.clear_caches()
    assert str(root).encode() in before
    assert str(root).encode() not in after
    assert b"src/repro/kernels/selective_copy.py" in after
