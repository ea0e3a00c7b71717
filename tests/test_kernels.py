"""Per-kernel validation: the Pallas kernels (interpret mode on CPU) against
their pure-jnp implementations, and those implementations against brute
force, sweeping shapes and dtypes.  Plus hypothesis property tests on the
ragged expansion primitive."""

import numpy as np
import pytest

from conftest import given, settings, st

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.bitmap_filter import bitmap_superset_pallas
from repro.kernels.sorted_intersect import tile_membership_pallas


# --------------------------------------------------------------------- +INT
@pytest.mark.parametrize("r,ta,tb", [(1, 1, 1), (4, 8, 16), (33, 7, 129),
                                     (256, 1, 64), (100, 128, 128)])
def test_tile_membership_shapes(r, ta, tb):
    rng = np.random.default_rng(r * 1000 + ta + tb)
    a = rng.integers(-1, 40, size=(r, ta)).astype(np.int32)
    b = rng.integers(-1, 40, size=(r, tb)).astype(np.int32)
    b = np.where(b < 0, -2, b).astype(np.int32)  # pad value
    got = np.asarray(tile_membership_pallas(jnp.asarray(a), jnp.asarray(b),
                                            interpret=True))
    want = np.asarray(ref.tile_membership_ref(jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_array_equal(got, want)


@given(st.integers(1, 40), st.integers(1, 24), st.integers(1, 24),
       st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_tile_membership_property(r, ta, tb, seed):
    rng = np.random.default_rng(seed)
    a = rng.integers(-1, 20, size=(r, ta)).astype(np.int32)
    b = rng.integers(-1, 20, size=(r, tb)).astype(np.int32)
    got = np.asarray(tile_membership_pallas(jnp.asarray(a), jnp.asarray(b),
                                            interpret=True, row_tile=16))
    for i in range(r):
        bset = set(int(x) for x in b[i] if x >= 0)
        for j in range(ta):
            want = a[i, j] >= 0 and int(a[i, j]) in bset
            assert bool(got[i, j]) == want


# ------------------------------------------------------------- edge_exists
@pytest.mark.parametrize("m,b", [(1, 1), (17, 5), (1000, 64), (4096, 1024),
                                 (100, 2048)])
def test_edge_exists_shapes(m, b):
    rng = np.random.default_rng(m + b)
    nbr = np.sort(rng.integers(0, 500, size=m)).astype(np.int32)
    lo = rng.integers(0, m, size=b).astype(np.int32)
    hi = np.minimum(m, lo + rng.integers(0, 50, size=b)).astype(np.int32)
    tgt = rng.integers(0, 500, size=b).astype(np.int32)
    want = np.asarray(ref.edge_exists_ref(jnp.asarray(nbr), jnp.asarray(lo),
                                          jnp.asarray(hi), jnp.asarray(tgt)))
    brute = np.array([tgt[i] in nbr[lo[i]:hi[i]] for i in range(b)])
    np.testing.assert_array_equal(want, brute)


@given(st.integers(1, 200), st.integers(1, 64), st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_edge_exists_property(m, b, seed):
    rng = np.random.default_rng(seed)
    nbr = np.sort(rng.integers(0, 60, size=m)).astype(np.int32)
    lo = rng.integers(0, m + 1, size=b).astype(np.int32)
    hi = np.clip(lo + rng.integers(-2, 30, size=b), 0, m).astype(np.int32)
    tgt = rng.integers(-1, 60, size=b).astype(np.int32)
    got = np.asarray(ops.edge_exists(jnp.asarray(nbr), jnp.asarray(lo),
                                     jnp.asarray(hi), jnp.asarray(tgt)))
    brute = np.array([hi[i] > lo[i] and tgt[i] in nbr[lo[i]:hi[i]]
                      for i in range(b)])
    np.testing.assert_array_equal(got, brute)


# ------------------------------------------------------------ bitmap filter
@pytest.mark.parametrize("b,w", [(1, 1), (7, 2), (1000, 4), (2049, 1)])
def test_bitmap_superset_shapes(b, w):
    rng = np.random.default_rng(b * 7 + w)
    bm = rng.integers(0, 2**32, size=(b, w), dtype=np.uint64).astype(np.uint32)
    req = rng.integers(0, 2**10, size=(w,), dtype=np.uint64).astype(np.uint32)
    got = np.asarray(bitmap_superset_pallas(jnp.asarray(bm), jnp.asarray(req),
                                            interpret=True, tile=512))
    want = np.asarray(ref.bitmap_superset_ref(jnp.asarray(bm), jnp.asarray(req)))
    np.testing.assert_array_equal(got, want)
    brute = np.all((bm & req) == req, axis=-1)
    np.testing.assert_array_equal(want, brute)


# -------------------------------------------------------- signature filter
@pytest.mark.parametrize("v,w,b", [(1, 2, 1), (17, 2, 5), (100, 4, 257),
                                   (1024, 8, 2048)])
def test_signature_filter_shapes(v, w, b):
    rng = np.random.default_rng(v * 13 + w + b)
    sig = rng.integers(0, 2**32, size=(v, w), dtype=np.uint64) \
        .astype(np.uint32)
    cand = rng.integers(-1, v, size=b).astype(np.int32)
    req = (rng.integers(0, 2**32, size=w, dtype=np.uint64)
           & rng.integers(0, 2**32, size=w, dtype=np.uint64)).astype(np.uint32)
    want = np.asarray(ref.signature_filter_ref(jnp.asarray(sig),
                                               jnp.asarray(cand),
                                               jnp.asarray(req)))
    brute = np.all((sig[np.clip(cand, 0, v - 1)] & req) == req, axis=-1)
    np.testing.assert_array_equal(want, brute)


@given(st.integers(1, 50), st.integers(1, 6), st.integers(1, 100),
       st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_signature_filter_property(v, w, b, seed):
    rng = np.random.default_rng(seed)
    sig = rng.integers(0, 2**32, size=(v, w), dtype=np.uint64) \
        .astype(np.uint32)
    cand = rng.integers(0, v, size=b).astype(np.int32)
    req = (rng.integers(0, 2**32, size=w, dtype=np.uint64)
           & rng.integers(0, 2**32, size=w, dtype=np.uint64)).astype(np.uint32)
    got = np.asarray(ops.signature_filter(jnp.asarray(sig), jnp.asarray(cand),
                                          jnp.asarray(req)))
    for i in range(b):
        want = bool(np.all((sig[cand[i]] & req) == req))
        assert bool(got[i]) == want


# ---------------------------------------------------------- segment gather
def _brute_segment_sum(table, idx, weights=None):
    """Per-segment sum of ``table`` rows over a fixed-hotness ``idx``
    ([S, K], -1 padded), in float64."""
    out = np.zeros((idx.shape[0], table.shape[1]))
    for i in range(idx.shape[0]):
        for j in range(idx.shape[1]):
            if idx[i, j] >= 0:
                w = 1.0 if weights is None else float(weights[i, j])
                out[i] += table[idx[i, j]].astype(np.float64) * w
    return out


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize("v,d,s,k", [(16, 8, 4, 3), (100, 64, 32, 8),
                                     (50, 200, 7, 1), (512, 128, 256, 16)])
def test_segment_gather_fixed(v, d, s, k, dtype):
    rng = np.random.default_rng(v + d + s + k)
    table = rng.normal(size=(v, d)).astype(np.float32)
    idx = rng.integers(-1, v, size=(s, k)).astype(np.int32)
    tj = jnp.asarray(table, dtype=dtype)
    # the fixed-hotness layout, flattened to the ragged form
    seg, col = np.nonzero(idx >= 0)
    got = ref.segment_gather_sum_ref(
        tj, jnp.asarray(idx[seg, col]), jnp.asarray(seg.astype(np.int32)), s)
    want = _brute_segment_sum(np.asarray(tj, np.float32), idx)
    if dtype == np.float32:
        rtol, atol = 1e-5, 1e-5
    else:  # bf16: accumulation-order differences scale with sqrt(k)
        rtol, atol = 0.08, 0.08 * np.sqrt(k)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=rtol, atol=atol)


def test_segment_gather_weighted():
    rng = np.random.default_rng(0)
    table = rng.normal(size=(32, 16)).astype(np.float32)
    idx = rng.integers(0, 32, size=(8, 4)).astype(np.int32)
    w = rng.normal(size=(8, 4)).astype(np.float32)
    seg = np.repeat(np.arange(8, dtype=np.int32), 4)
    got = ref.segment_gather_sum_ref(jnp.asarray(table),
                                     jnp.asarray(idx.reshape(-1)),
                                     jnp.asarray(seg), 8,
                                     weights=jnp.asarray(w.reshape(-1)))
    np.testing.assert_allclose(np.asarray(got), _brute_segment_sum(table, idx, w),
                               rtol=1e-5, atol=1e-5)


def test_segment_gather_ragged_entry():
    rng = np.random.default_rng(3)
    table = rng.normal(size=(64, 32)).astype(np.float32)
    e, s = 100, 16
    indices = rng.integers(0, 64, size=e).astype(np.int32)
    segments = rng.integers(0, s, size=e).astype(np.int32)
    got = ops.segment_gather_sum(jnp.asarray(table), jnp.asarray(indices),
                                 jnp.asarray(segments), s)
    want = np.zeros((s, 32))
    np.add.at(want, segments, table[indices].astype(np.float64))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------- expand/filter/compact
def _efc_case(rng, r, v, w, tile, with_mask, with_bid):
    degs = rng.integers(0, 6, r).astype(np.int32)
    offs = np.concatenate([[0], np.cumsum(degs)[:-1]]).astype(np.int32)
    total = int(degs.sum())
    m = max(1, total + int(rng.integers(0, 8)))
    nbr = rng.integers(0, v, m).astype(np.int32)
    start = rng.integers(0, max(1, m - 6), r).astype(np.int32)
    bitmap = rng.integers(0, 2**32, (v, w), dtype=np.uint64).astype(np.uint32)
    mask = (rng.integers(0, 2**3, w, dtype=np.uint64).astype(np.uint32)
            if with_mask else np.zeros(w, np.uint32))
    bid = np.int32(rng.integers(0, v)) if with_bid else np.int32(-1)
    cap = tile * max(1, -(-max(1, total) // tile))  # multiple of tile ≥ total
    return (jnp.asarray(nbr), jnp.asarray(bitmap), jnp.asarray(start),
            jnp.asarray(degs), jnp.asarray(offs), jnp.asarray(mask),
            jnp.asarray(bid)), cap


def _check_efc_brute(args, cap):
    """The dispatched fused kernel against a brute-force survivor stream."""
    nbr, bitmap, start, degs, offs, mask, bid = map(np.asarray, args)
    got_v, got_r, got_c = ops.expand_filter_compact(*args, cap)
    stream = []
    for i in range(degs.shape[0]):
        for j in range(degs[i]):
            k = int(start[i]) + j
            if k >= nbr.shape[0]:
                continue
            vv = int(nbr[k])
            if not all((bitmap[vv] & mask) == mask):
                continue
            if int(bid) >= 0 and vv != int(bid):
                continue
            stream.append((vv, i))
    assert int(got_c) == len(stream)
    for k, (vv, rr) in enumerate(stream):
        assert int(got_v[k]) == vv and int(got_r[k]) == rr
    assert all(int(x) == -1 for x in np.asarray(got_v)[len(stream):])


@pytest.mark.parametrize("r,v,w,tile", [(1, 4, 1, 8), (17, 30, 2, 16),
                                        (40, 64, 1, 32), (5, 8, 4, 8)])
@pytest.mark.parametrize("with_mask,with_bid", [(False, False), (True, False),
                                                (True, True)])
def test_expand_filter_compact_shapes(r, v, w, tile, with_mask, with_bid):
    rng = np.random.default_rng(r * 100 + v + w + tile)
    _check_efc_brute(*_efc_case(rng, r, v, w, tile, with_mask, with_bid))


@given(st.integers(1, 40), st.integers(2, 40), st.integers(1, 2),
       st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_expand_filter_compact_property(r, v, w, seed):
    """The fused kernel vs brute force on random ragged CSR slices, label
    bitmaps, and bound-id probes."""
    rng = np.random.default_rng(seed)
    _check_efc_brute(*_efc_case(rng, r, v, w, 16, with_mask=bool(seed % 2),
                                with_bid=seed % 3 == 0))


# ------------------------------------------------- prefix sum, compaction
@pytest.mark.parametrize("n", [1, 7, ref.SCAN_BLOCK, ref.SCAN_BLOCK + 1,
                               3 * ref.SCAN_BLOCK - 5])
def test_prefix_sum_and_compact_index(n):
    rng = np.random.default_rng(n)
    x = rng.integers(-3, 9, n).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(ref.prefix_sum(jnp.asarray(x))),
                                  np.cumsum(x))
    ok = rng.random(n) < 0.3
    src = np.asarray(ref.compact_index(jnp.asarray(ok)))
    kept = np.flatnonzero(ok)
    np.testing.assert_array_equal(src[:kept.size], kept)
    assert ((0 <= src) & (src < n)).all()


# ------------------------------------------------------------ ragged expand
@given(st.lists(st.integers(0, 9), min_size=1, max_size=30),
       st.integers(1, 128))
@settings(max_examples=40, deadline=None)
def test_ragged_expand_property(degs, extra_cap):
    degs_np = np.asarray(degs, dtype=np.int32)
    total = int(degs_np.sum())
    cap = total + extra_cap
    offs = np.concatenate([[0], np.cumsum(degs_np)[:-1]]).astype(np.int32)
    row, j, valid = ref.ragged_expand_ref(jnp.asarray(offs),
                                          jnp.asarray(degs_np), cap)
    row, j, valid = map(np.asarray, (row, j, valid))
    assert valid.sum() == total
    # every (row, j) pair with j < deg appears exactly once
    want = {(r, x) for r, d in enumerate(degs) for x in range(d)}
    got = {(int(row[k]), int(j[k])) for k in range(cap) if valid[k]}
    assert got == want


# ------------------------------------------------------------- delta_merge
def _brute_delta_merge(base, delta, tomb, bs, bd, ds, tlo, thi, j, valid):
    v = np.full(j.shape, -1, np.int32)
    ok = np.zeros(j.shape, bool)
    for k in range(j.shape[0]):
        if not valid[k]:
            continue
        if j[k] < bd[k]:
            cand = base[bs[k] + j[k]]
            dead = cand in set(tomb[tlo[k]:thi[k]])
        else:
            cand = delta[ds[k] + (j[k] - bd[k])]
            dead = False
        v[k] = cand
        ok[k] = not dead
    return v, ok


def _delta_merge_case(rng, k, mb, md, mt):
    base = np.sort(rng.integers(0, 60, size=mb)).astype(np.int32)
    delta = np.sort(rng.integers(0, 60, size=md)).astype(np.int32)
    # tombstones: sorted runs drawn from base values
    tomb = np.sort(rng.choice(base, size=min(mt, mb),
                              replace=False)).astype(np.int32)
    bd = rng.integers(0, 5, size=k).astype(np.int32)
    dd = rng.integers(0, 4, size=k).astype(np.int32)
    bs = rng.integers(0, max(1, mb - 5), size=k).astype(np.int32)
    ds = rng.integers(0, max(1, md - 4), size=k).astype(np.int32)
    tlo = rng.integers(0, tomb.shape[0] + 1, size=k).astype(np.int32)
    thi = np.minimum(tomb.shape[0],
                     tlo + rng.integers(0, 4, size=k)).astype(np.int32)
    j = rng.integers(0, 8, size=k).astype(np.int32)
    valid = (j < bd + dd) & (rng.random(k) > 0.1)
    return base, delta, tomb, bs, bd, ds, tlo, thi, j, valid


@pytest.mark.parametrize("k,mb,md,mt", [(1, 8, 4, 2), (64, 200, 30, 40),
                                        (1000, 4096, 257, 600)])
def test_delta_merge_oracle_vs_brute(k, mb, md, mt):
    rng = np.random.default_rng(k + mb)
    case = _delta_merge_case(rng, k, mb, md, mt)
    base, delta, tomb, bs, bd, ds, tlo, thi, j, valid = case
    got_v, got_ok = ref.delta_merge_ref(
        jnp.asarray(base), jnp.asarray(delta), jnp.asarray(tomb),
        jnp.asarray(bs), jnp.asarray(bd), jnp.asarray(ds),
        jnp.asarray(tlo), jnp.asarray(thi), jnp.asarray(j),
        jnp.asarray(valid))
    want_v, want_ok = _brute_delta_merge(base, delta, tomb, bs, bd, ds,
                                         tlo, thi, j, valid)
    np.testing.assert_array_equal(np.asarray(got_v), want_v)
    np.testing.assert_array_equal(np.asarray(got_ok) & np.asarray(valid),
                                  want_ok & valid)


def test_delta_merge_labeled_composite_masking():
    # base plain CSR of one source: neighbors (2, el 0), (2, el 1), (3, el 0)
    base_nbr = jnp.asarray(np.array([2, 2, 3], np.int32))
    base_lab = jnp.asarray(np.array([0, 1, 0], np.int32))
    delta_nbr = jnp.asarray(np.array([9], np.int32))
    delta_lab = jnp.asarray(np.array([1], np.int32))
    n_el = 2
    # tombstone exactly (nbr=2, el=1) -> key 5
    tomb_key = jnp.asarray(np.array([5], np.int32))
    k = 4
    z = lambda v: jnp.asarray(np.full(k, v, np.int32))  # noqa: E731
    j = jnp.asarray(np.arange(k, dtype=np.int32))
    v, el, ok = ref.delta_merge_labeled_ref(
        base_nbr, base_lab, delta_nbr, delta_lab, tomb_key,
        z(0), z(3), z(0), z(0), z(1), j,
        jnp.asarray(np.ones(k, bool)), n_el)
    np.testing.assert_array_equal(np.asarray(v), [2, 2, 3, 9])
    np.testing.assert_array_equal(np.asarray(el), [0, 1, 0, 1])
    # only the (2, el=1) candidate is tombstoned; delta slot never is
    np.testing.assert_array_equal(np.asarray(ok), [True, False, True, True])


# ----------------------------------------------------------- dispatch table
def test_dispatch_table_modes(monkeypatch):
    assert set(ops.TPU_IMPL.values()) <= {ops.PALLAS, ops.XLA}
    assert jax.default_backend() != "tpu"
    monkeypatch.delenv("REPRO_KERNELS", raising=False)
    assert {ops.impl(k) for k in ops.TPU_IMPL} == {ops.XLA}  # off a TPU
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    assert {ops.impl(k) for k in ops.TPU_IMPL} == {ops.XLA}
    monkeypatch.setenv("REPRO_KERNELS", "pallas_interpret")
    assert ops.impl("bitmap_superset") == ops.PALLAS_INTERPRET
    assert ops.impl("expand_filter") == ops.XLA  # no Pallas version
    monkeypatch.setenv("REPRO_KERNELS", "pallas")  # compiled Pallas: TPU only
    with pytest.raises(RuntimeError, match="needs a TPU"):
        ops.impl("bitmap_superset")
    monkeypatch.setenv("REPRO_KERNELS", "mosaic")
    with pytest.raises(ValueError):
        ops.impl("bitmap_superset")
    # on a TPU the table decides, kernel by kernel
    monkeypatch.delenv("REPRO_KERNELS")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert {k: ops.impl(k) for k in ops.TPU_IMPL} == ops.TPU_IMPL


def test_record_kernels_per_step(monkeypatch):
    """Dispatches are recorded per step, once each, with their
    implementation, and only while recording is on."""
    monkeypatch.setenv("REPRO_KERNELS", "pallas_interpret")
    bm = jnp.zeros((8, 1), jnp.uint32)
    req = jnp.zeros((1,), jnp.uint32)
    with ops.record_kernels() as steps:
        ops.record_step(0)
        ops.bitmap_superset(bm, req)
        ops.bitmap_superset(bm, req)
        ops.record_step(1)
        ops.ragged_expand(jnp.zeros(2, jnp.int32), jnp.ones(2, jnp.int32), 4)
        ops.bitmap_superset(bm, req)
    want = {0: ["bitmap_superset:pallas_interpret"],
            1: ["ragged_expand:xla", "bitmap_superset:pallas_interpret"]}
    assert steps == want
    ops.bitmap_superset(bm, req)
    assert steps == want
