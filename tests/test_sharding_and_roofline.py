"""Sharding rule resolution + HLO analyzer unit tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.common.sharding import (
    DEFAULT_RULES,
    fsdp2d_rules,
    spec_for,
    tree_shardings,
)
from repro.roofline.hlo_analysis import (
    analyze_hlo_text,
    collective_bytes,
    parse_hlo,
    shape_bytes,
)


def _mesh(shape=(2, 4), axes=("data", "model")):
    return jax.sharding.AbstractMesh(tuple(shape), tuple(axes))


class TestSpecFor:
    def test_divisible_shards(self):
        m = _mesh()
        assert spec_for((8, 64), ("batch", None), m) == P("data", None)
        assert spec_for((16, 32), ("fsdp", "tensor"), m) == P("data", "model")

    def test_non_divisible_replicates(self):
        m = _mesh()
        # 6 % 4 != 0 -> tensor dim replicated rather than erroring
        assert spec_for((16, 6), ("fsdp", "tensor"), m) == P("data", None)
        assert spec_for((3, 6), ("fsdp", "tensor"), m) == P(None, None)

    def test_axis_used_once(self):
        m = _mesh()
        # both dims map to 'model': only the first claims it
        spec = spec_for((8, 8), ("tensor", "act_heads"), m)
        assert spec == P("model", None)

    def test_multi_axis_claim(self):
        m = _mesh((2, 4), ("pod", "data"))
        spec = spec_for((8, 4), ("batch", None), m)
        assert spec == P(("pod", "data"), None)

    def test_fsdp2d_prefix_divisibility(self):
        m = _mesh((2, 16, 16), ("pod", "data", "model"))
        r = fsdp2d_rules()
        # batch 256 claims (data, model) = 256 but NOT pod (256 % 512 != 0)
        assert spec_for((256, 128), ("batch", None), m, r) == \
            P(("data", "model"), None)


class TestHloAnalyzer:
    def test_shape_bytes(self):
        assert shape_bytes("f32[4,8]{1,0}") == 128
        assert shape_bytes("bf16[10]") == 20
        assert shape_bytes("(f32[2,2]{1,0}, s32[3])") == 28
        assert shape_bytes("pred[7]") == 7

    def test_collective_bytes_ring_model(self):
        import re

        from repro.roofline.hlo_analysis import Instruction

        inst = Instruction(
            "ag", "f32[64,64]{1,0}", "all-gather", ["x"],
            '%ag = f32[64,64]{1,0} all-gather(%x), replica_groups=[4,8]<=[32]')
        kind, naive, ring = collective_bytes(inst)
        assert kind == "all-gather"
        assert naive == 64 * 64 * 4 // 8
        assert ring == 64 * 64 * 4 * 7 // 8

    def test_scan_trip_count_correction(self):
        """FLOPs of a scanned matmul must scale with scan length."""
        def f(w, x):
            def body(x, wi):
                return jnp.tanh(x @ wi), None
            return jax.lax.scan(body, x, w)[0].sum()

        w = jax.ShapeDtypeStruct((7, 32, 32), jnp.float32)
        x = jax.ShapeDtypeStruct((16, 32), jnp.float32)
        txt = jax.jit(f).lower(w, x).compile().as_text()
        costs = analyze_hlo_text(txt)
        expected = 7 * 2 * 16 * 32 * 32
        assert abs(costs.flops - expected) / expected < 0.05
        assert 7 in costs.trip_counts

    def test_parse_computations(self):
        def f(x):
            return jnp.sin(x) @ x.T

        txt = jax.jit(f).lower(
            jax.ShapeDtypeStruct((32, 32), jnp.float32)).compile().as_text()
        comps = parse_hlo(txt)
        assert len(comps) >= 1
        costs = analyze_hlo_text(txt)
        assert costs.flops >= 2 * 32 * 32 * 32


class TestModelFlops:
    def test_dense_train_close_to_6nd(self):
        from repro.configs import get_config
        from repro.common.types import TRAIN_4K
        from repro.roofline.analysis import model_flops, matmul_params

        cfg = get_config("phi3-mini-3.8b")
        mf = model_flops(cfg, TRAIN_4K)
        n = matmul_params(cfg)
        tokens = 256 * 4096
        assert mf > 6 * n * tokens  # attention + logits on top
        assert mf < 6 * n * tokens * 1.8
