"""Pallas kernel validation: shape/dtype sweeps + hypothesis properties,
asserting allclose against the pure-jnp oracles in kernels/ref.py
(interpret=True executes the TPU kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.kernels import ops
from repro.kernels import ref as R

RNG = np.random.default_rng(7)


def randn(shape, dtype=jnp.float32):
    return jnp.array(RNG.standard_normal(shape), dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,hq,hkv,s,hd,blk", [
    (1, 1, 1, 128, 64, 64),
    (2, 4, 2, 256, 64, 128),
    (1, 8, 8, 128, 128, 64),   # MHA
    (2, 6, 2, 128, 32, 64),    # GQA group 3
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_shapes(b, hq, hkv, s, hd, blk, dtype):
    q, k, v = (randn((b, hq, s, hd), dtype), randn((b, hkv, s, hd), dtype),
               randn((b, hkv, s, hd), dtype))
    out = ops.flash_attention(q, k, v, causal=True, impl="interpret",
                              block_q=blk, block_k=blk)
    want = R.flash_attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.array(out, np.float32),
                               np.array(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [16, 64, 100])
def test_flash_window(window):
    q, k, v = randn((1, 2, 256, 32)), randn((1, 2, 256, 32)), randn((1, 2, 256, 32))
    out = ops.flash_attention(q, k, v, causal=True, window=window,
                              impl="interpret", block_q=64, block_k=64)
    want = R.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.array(out), np.array(want), atol=2e-5, rtol=2e-5)


def test_flash_noncausal():
    q, k, v = randn((2, 2, 128, 32)), randn((2, 2, 128, 32)), randn((2, 2, 128, 32))
    out = ops.flash_attention(q, k, v, causal=False, impl="interpret",
                              block_q=64, block_k=64)
    want = R.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.array(out), np.array(want), atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# paged attention
# ---------------------------------------------------------------------------

def _paged_setup(b, hq, hkv, hd, page, pps, p_total, seq_lens):
    q = randn((b, hq, hd))
    pool = randn((p_total, page, 2, hkv, hd))
    tables = np.full((b, pps), -1, np.int32)
    page_pos = np.full((b, pps), -(2 ** 20), np.int32)
    ctr = 0
    for i in range(b):
        for j in range(seq_lens[i] // page + 1):
            tables[i, j] = ctr % p_total
            page_pos[i, j] = j * page
            ctr += 1
    return q, pool, jnp.array(tables), jnp.array(page_pos), jnp.array(seq_lens, jnp.int32)


@pytest.mark.parametrize("b,hq,hkv,hd,page", [
    (2, 4, 2, 64, 16), (3, 8, 8, 32, 8), (1, 6, 1, 128, 32),
])
def test_paged_matches_ref(b, hq, hkv, hd, page):
    seq = RNG.integers(1, page * 3, b)
    q, pool, tbl, pp, sl = _paged_setup(b, hq, hkv, hd, page, 4, 24, seq)
    got = ops.paged_attention(q, pool, tbl, pp, sl, impl="interpret")
    want = R.paged_attention_ref(q, pool, tbl, pp, sl)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.array(g), np.array(w), atol=1e-4, rtol=1e-4)


def test_paged_equals_dense_attention():
    """Combined partials must equal full attention over the logical KV."""
    b, hq, hkv, hd, page = 2, 4, 2, 32, 8
    seq = np.array([20, 13])
    q, pool, tbl, pp, sl = _paged_setup(b, hq, hkv, hd, page, 6, 32, seq)
    acc, m, l = ops.paged_attention(q, pool, tbl, pp, sl, impl="interpret")
    out = np.array(acc / np.maximum(np.array(l), 1e-30)[..., None])
    # dense reference: rebuild contiguous KV from pages
    for i in range(b):
        ln = seq[i] + 1
        kk = np.zeros((ln, hkv, hd), np.float32)
        vv = np.zeros((ln, hkv, hd), np.float32)
        for j in range(ln // page + 1):
            pid = int(tbl[i, j])
            if pid < 0:
                continue
            lo = j * page
            hi = min(lo + page, ln)
            kk[lo:hi] = np.array(pool[pid, : hi - lo, 0])
            vv[lo:hi] = np.array(pool[pid, : hi - lo, 1])
        qg = np.array(q[i]).reshape(hkv, hq // hkv, hd) / np.sqrt(hd)
        s = np.einsum("hgd,thd->hgt", qg, kk)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        o = np.einsum("hgt,thd->hgd", p, vv).reshape(hq, hd)
        np.testing.assert_allclose(out[i], o, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# selective copy
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.data())
def test_selective_copy_property(data):
    """Metadata lands in the compact buffer; payload lands in its pages;
    untouched pages are preserved — for arbitrary parse boundaries."""
    b = data.draw(st.integers(1, 3))
    page = data.draw(st.sampled_from([8, 16]))
    pps = 4
    s = 16 + pps * page
    p_total = b * pps + 2
    meta_max = 16
    stream = jnp.array(RNG.integers(1, 1000, (b, s)), jnp.int32)
    meta_len, total_len, tables = [], [], np.full((b, pps), -1, np.int32)
    ctr = 0
    for i in range(b):
        ml = data.draw(st.integers(0, meta_max))
        pl_len = data.draw(st.integers(0, pps * page))
        meta_len.append(ml)
        total_len.append(ml + pl_len)
        for j in range(-(-pl_len // page)):
            tables[i, j] = ctr
            ctr += 1
    meta_len = jnp.array(meta_len, jnp.int32)
    total_len = jnp.array(total_len, jnp.int32)
    pool = jnp.array(RNG.integers(0, 5, (p_total, page)), jnp.int32)
    got_m, got_p = ops.selective_copy(stream, meta_len, total_len, pool,
                                      jnp.array(tables), meta_max=meta_max,
                                      impl="interpret")
    want_m, want_p = R.selective_copy_ref(stream, meta_len, total_len, pool,
                                          jnp.array(tables), meta_max=meta_max)
    assert np.array_equal(np.array(got_m), np.array(want_m))
    assert np.array_equal(np.array(got_p), np.array(want_p))
    # semantic checks against the raw stream (payload from lane meta_max)
    for i in range(b):
        ml, tl = int(meta_len[i]), int(total_len[i])
        assert np.array_equal(np.array(got_m[i, :ml]), np.array(stream[i, :ml]))
        for j, pid in enumerate(tables[i]):
            if pid < 0:
                continue
            lo = meta_max + j * page
            hi = min(lo + page, meta_max + tl - ml)
            if hi > lo:
                assert np.array_equal(np.array(got_p[pid, : hi - lo]),
                                      np.array(stream[i, lo:hi]))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_selective_copy_reserved_scratch_bitexact(seed):
    """The fused kernel running over the pool's reserved scratch row (the
    zero-realloc hot path) stays bit-exact with the oracle — including the
    scratch row itself, which must come back untouched."""
    from repro.kernels.selective_copy import selective_copy
    from repro.kernels.testing import selcopy_case

    stream, ml, tl, pool, tables = selcopy_case(np.random.default_rng(seed))
    got_m, got_p = selective_copy(stream, ml, tl, pool, tables, meta_max=16,
                                  interpret=True, reserved_scratch=True)
    want_m, want_p = R.selective_copy_ref(stream, ml, tl, pool, tables,
                                          meta_max=16)
    assert got_p.shape == pool.shape         # scratch row kept in place
    assert np.array_equal(np.array(got_m), np.array(want_m))
    assert np.array_equal(np.array(got_p), np.array(want_p))


def test_selective_copy_hot_path_has_no_pool_copy():
    """Regression for the fused zero-realloc datapath: with the reserved
    scratch row the trace must contain exactly ONE pallas_call (meta +
    payload fused) and no concatenate/pad (the old implementation extended
    the pool by a dummy row — an O(pool) copy — on every invocation)."""
    import functools

    from repro.kernels.selective_copy import selective_copy
    from repro.kernels.testing import (
        POOL_COPY_PRIMS,
        jaxpr_primitives,
        selcopy_case,
    )

    stream, ml, tl, pool, tables = selcopy_case(np.random.default_rng(0))
    fn = functools.partial(selective_copy, meta_max=16, interpret=True,
                           reserved_scratch=True)
    names = jaxpr_primitives(jax.make_jaxpr(fn)(stream, ml, tl, pool,
                                                tables).jaxpr)
    assert names.count("pallas_call") == 1     # single fused dispatch
    assert not set(names) & set(POOL_COPY_PRIMS)
    # the legacy (scratch-less) path still shows its copy — keeps this
    # test honest about what it detects
    legacy = functools.partial(selective_copy, meta_max=16, interpret=True,
                               reserved_scratch=False)
    lnames = jaxpr_primitives(jax.make_jaxpr(legacy)(stream, ml, tl,
                                                     pool[:-1], tables).jaxpr)
    assert "concatenate" in lnames


@pytest.mark.parametrize("meta_len_max,page,want", [
    (11, 16, 16), (11, 8, 16), (0, 16, 16), (20, 96, 32),
    (129, 128, 256), (300, 4096, 384)])
def test_stream_meta_width_aligns_to_page_and_lane(meta_len_max, page, want):
    """The metadata region is padded to ``gcd(page, 128)``: a whole lane
    tile at every page size the compiled kernels accept, and no wider than
    the page's own alignment on the CPU paths' small pages."""
    from repro.kernels.selective_copy import stream_meta_width

    assert stream_meta_width(meta_len_max, page) == want


@pytest.mark.parametrize("page,meta_max", [(8, 16), (128, 16), (96, 128)])
def test_compiled_selective_copy_refuses_unaligned_layout(page, meta_max):
    """The compiled (non-interpret) kernels move pages and slice the stream
    at lane-tile offsets only: any other page or metadata width raises —
    through ``ops`` too — instead of falling back to another impl."""
    from repro.kernels.testing import fused_round_case

    case = fused_round_case(np.random.default_rng(1), page=page,
                            meta_max=meta_max)
    base = (case["stream"], case["meta_len"], case["total_len"],
            case["pool"], case["tables"])
    with pytest.raises(ValueError, match="multiples of 128"):
        ops.fused_round(*base, meta_max=meta_max, impl="pallas")
    with pytest.raises(ValueError, match="multiples of 128"):
        ops.selective_copy(*base, meta_max=meta_max, impl="pallas",
                           reserved_scratch=True)
    if page % 128:
        with pytest.raises(ValueError, match="multiples of 128"):
            ops.selective_gather(case["pool"], case["tables"],
                                 case["meta_len"], impl="pallas")


# ---------------------------------------------------------------------------
# selective gather (egress mirror)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,page,pps", [(1, 8, 2), (2, 8, 4), (3, 16, 3),
                                        (4, 8, 1)])
@pytest.mark.parametrize("with_ks", [False, True])
def test_selective_gather_matches_ref(b, page, pps, with_ks):
    """The fused egress gather (interpret mode) is bit-exact with its
    oracle, with and without the hw-kTLS TX keystream operand."""
    from repro.kernels.selective_copy import selective_gather
    from repro.kernels.testing import selgather_case

    pool, tables, lengths, ks = selgather_case(
        np.random.default_rng(11 * b + pps), b=b, page=page, pps=pps)
    k = ks if with_ks else None
    got = selective_gather(pool, tables, lengths, interpret=True, keystream=k)
    want = R.selective_gather_ref(pool, tables, lengths, k)
    assert np.array_equal(np.array(got), np.array(want))
    # semantic check: each valid page slot j carries payload span
    # [j*page, (j+1)*page) of its source page, XORed with the keystream
    host = np.array(got)
    for i in range(b):
        ln = int(lengths[i])
        assert not host[i, ln:].any()            # zero past the length
        for j, pid in enumerate(np.array(tables[i])):
            lo, hi = j * page, min((j + 1) * page, ln)
            if pid < 0 or hi <= lo:
                continue
            want_span = np.array(pool[pid, : hi - lo])
            if with_ks:
                want_span = np.bitwise_xor(want_span,
                                           np.array(ks[i, lo:hi]))
            assert np.array_equal(host[i, lo:hi], want_span)


def test_selective_gather_reads_pool_in_place():
    """The gather's jaxpr must contain one fused dispatch and no
    pool-sized copy (no concatenate/pad): the resident pool is read
    where it lives."""
    import functools

    from repro.kernels.selective_copy import selective_gather
    from repro.kernels.testing import (
        POOL_COPY_PRIMS,
        jaxpr_primitives,
        selgather_case,
    )

    pool, tables, lengths, ks = selgather_case(np.random.default_rng(0))
    for k in (None, ks):
        fn = functools.partial(selective_gather, interpret=True, keystream=k)
        names = jaxpr_primitives(jax.make_jaxpr(fn)(pool, tables,
                                                    lengths).jaxpr)
        assert names.count("pallas_call") == 1
        assert not set(names) & set(POOL_COPY_PRIMS)


def test_selective_gather_ops_dispatch():
    from repro.kernels.testing import selgather_case

    pool, tables, lengths, ks = selgather_case(np.random.default_rng(5))
    want = R.selective_gather_ref(pool, tables, lengths, ks)
    for impl in ("ref", "interpret"):
        got = ops.selective_gather(pool, tables, lengths, impl=impl,
                                   keystream=ks)
        assert np.array_equal(np.array(got), np.array(want)), impl


# ---------------------------------------------------------------------------
# mlstm scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,s,dh,chunk", [
    (1, 1, 64, 32, 16), (2, 3, 64, 32, 32), (1, 2, 128, 64, 16),
])
def test_mlstm_matches_sequential(b, h, s, dh, chunk):
    q, k, v = randn((b, h, s, dh)), randn((b, h, s, dh)), randn((b, h, s, dh))
    li = randn((b, h, s))
    lf = jnp.array(np.log(1 / (1 + np.exp(-(RNG.standard_normal((b, h, s)) + 2)))),
                   jnp.float32)
    got = ops.mlstm_scan(q, k, v, li, lf, chunk=chunk, impl="interpret")
    want = R.mlstm_scan_ref(q, k, v, li, lf)
    np.testing.assert_allclose(np.array(got), np.array(want), atol=5e-4,
                               rtol=5e-4)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 2), st.sampled_from([16, 32]))
def test_mlstm_gate_extremes(b, chunk):
    """Strong forget gates (decay ~0) and strong inputs stay stable."""
    h, s, dh = 2, 64, 16
    q, k, v = randn((b, h, s, dh)), randn((b, h, s, dh)), randn((b, h, s, dh))
    li = jnp.array(RNG.standard_normal((b, h, s)) * 4, jnp.float32)
    lf = jnp.array(np.log(1 / (1 + np.exp(-(RNG.standard_normal((b, h, s)) * 4)))),
                   jnp.float32)
    got = ops.mlstm_scan(q, k, v, li, lf, chunk=chunk, impl="interpret")
    want = R.mlstm_scan_ref(q, k, v, li, lf)
    assert np.all(np.isfinite(np.array(got)))
    np.testing.assert_allclose(np.array(got), np.array(want), atol=3e-3,
                               rtol=3e-3)
