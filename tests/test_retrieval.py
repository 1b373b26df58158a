"""Banded LSH over packed b-bit codes + the Hamming top-k kernel.

Covers the retrieval half of the dedup/retrieval subsystem: band-key
extraction straight from the packed bitstream (bit-exact vs the
unpacked reference, including non-byte-aligned b·r), the banded
inverted index's insert/query/delete lifecycle, and the
``hamming_topk`` op — Pallas vs XLA parity, dispatch-report presence,
and the ineligible-force fallback shared by every dispatched op.
"""
import numpy as np
import pytest

from repro.core.bbit import pack_codes, packed_width
from repro.kernels import dispatch, ops
from repro.kernels.hamming import (hamming_distance_pallas,
                                   hamming_distance_xla)
from repro.retrieval import BandedLSHIndex
from repro.retrieval.bands import (band_geometry, band_keys_packed,
                                   band_keys_ref, band_signature)


@pytest.fixture(autouse=True)
def _clean_dispatch(monkeypatch):
    monkeypatch.delenv(dispatch.ENV_DISPATCH, raising=False)
    dispatch.reset()
    yield
    dispatch.reset()


def _codes(n, k, b, seed=0):
    rng = np.random.default_rng(seed * 7919 + k * 31 + b)
    return rng.integers(0, 1 << b, size=(n, k)).astype(np.uint16)


# ---------------------------------------------------------------------------
# band keys


@pytest.mark.parametrize("b", [1, 2, 3, 4, 8, 12])
@pytest.mark.parametrize("r", [1, 2, 4])
def test_band_keys_packed_match_unpacked_reference(b, r):
    k = 24
    codes = _codes(17, k, b, seed=b * 10 + r)
    got = band_keys_packed(pack_codes(codes, b), k, b, r)
    want = band_keys_ref(codes, b, r)
    assert got.dtype == np.uint64
    assert got.shape == (17, k // r)
    np.testing.assert_array_equal(got, want)


def test_band_keys_unaligned_vs_whole_byte_paths():
    # r*b = 24 exercises the whole-byte fast path, r*b = 12 the
    # unaligned uint64 gather — same reference for both
    k, b = 16, 3
    codes = _codes(9, k, b)
    packed = pack_codes(codes, b)
    for r in (4, 8):
        np.testing.assert_array_equal(
            band_keys_packed(packed, k, b, r), band_keys_ref(codes, b, r))


def test_band_geometry_rejects_bad_shapes():
    assert band_geometry(16, 4, 4) == 4
    with pytest.raises(ValueError, match="divide"):
        band_geometry(16, 4, 3)
    with pytest.raises(ValueError, match="exceeds"):
        band_geometry(64, 8, 8)          # 64 band bits > 56
    with pytest.raises(ValueError, match=">= 1"):
        band_geometry(16, 4, 0)


def test_band_signature_is_prefix_of_band_keys():
    k, b, r = 16, 4, 2
    codes = _codes(3, k, b)
    packed = pack_codes(codes, b)
    keys = band_keys_packed(packed, k, b, r)
    sig = band_signature(packed[1], k, b, r, probe_bands=3)
    assert sig == tuple(int(x) for x in keys[1, :3])
    full = band_signature(packed[1], k, b, r)
    assert full == tuple(int(x) for x in keys[1])


# ---------------------------------------------------------------------------
# banded inverted index


def test_index_insert_query_delete_lifecycle():
    k, b, r = 16, 4, 2
    codes = _codes(40, k, b, seed=5)
    packed = pack_codes(codes, b)
    idx = BandedLSHIndex(k=k, b=b, rows_per_band=r)
    ids = [f"doc{i}" for i in range(40)]
    idx.insert(ids, packed)
    assert len(idx) == 40

    # an indexed row retrieves itself at rank 1, similarity exactly 1
    got_ids, sims = idx.query(packed[7], top_k=5)
    assert got_ids[0] == "doc7"
    assert sims[0] == pytest.approx(1.0)
    assert np.all(np.diff(sims) <= 1e-6)        # descending

    assert idx.delete(["doc7", "nope"]) == 1
    assert len(idx) == 39
    got_ids, _ = idx.query(packed[7], top_k=5)
    assert "doc7" not in got_ids

    st = idx.stats()
    assert st["entries"] == 39 and st["bands"] == k // r
    assert st["bytes_est"] > 0


def test_index_rejects_wrong_width():
    idx = BandedLSHIndex(k=16, b=4, rows_per_band=2)
    with pytest.raises(ValueError, match="width"):
        idx.query(np.zeros(3, np.uint8))


def test_index_near_duplicate_lands_in_topk():
    # flip one code of a row: differs in <= b bits of k*b, still
    # collides in most bands and ranks directly under the exact copy
    k, b, r = 32, 4, 2
    codes = _codes(64, k, b, seed=9)
    near = codes[3].copy()
    near[0] ^= 1
    idx = BandedLSHIndex(k=k, b=b, rows_per_band=r)
    idx.insert(list(range(64)), pack_codes(codes, b))
    ids, sims = idx.query(pack_codes(near[None, :], b)[0], top_k=5)
    assert 3 in ids
    assert sims[list(ids).index(3)] >= 1.0 - (b / (k * b)) - 1e-6


# ---------------------------------------------------------------------------
# hamming_topk kernel + dispatch


@pytest.mark.parametrize("b", [1, 2, 4, 8])
def test_hamming_pallas_matches_xla(b):
    k = 32
    codes = _codes(50, k, b, seed=b)
    packed = pack_codes(codes, b)
    q = packed[11]
    d_pal = np.asarray(hamming_distance_pallas(q, packed, interpret=True))
    d_xla = np.asarray(hamming_distance_xla(q, packed))
    np.testing.assert_array_equal(d_pal, d_xla)
    assert d_pal[11] == 0


def test_hamming_topk_matches_brute_force():
    k, b = 32, 4
    codes = _codes(60, k, b, seed=2)
    packed = pack_codes(codes, b)
    q = packed[0]
    idx, sims = ops.hamming_topk(q, packed, k=k, bits=b, topk=10)
    idx, sims = np.asarray(idx), np.asarray(sims)
    # brute force over unpacked codes' bitstream
    dist = np.asarray(hamming_distance_xla(q, packed))
    order = np.argsort(dist, kind="stable")[:10]
    np.testing.assert_array_equal(np.sort(dist[idx]), dist[order])
    np.testing.assert_allclose(sims, 1.0 - dist[idx] / (k * b), rtol=1e-6)
    assert idx[0] == 0 and sims[0] == pytest.approx(1.0)


def test_hamming_topk_in_dispatch_report_with_loud_fallback():
    shape = {"b": 8, "k": 32, "rows": 50, "width": 32}
    assert dispatch.choose("hamming_topk", shape) in ("pallas", "xla")
    rep = dispatch.dispatch_report()
    assert any(key.startswith("hamming_topk") for key in rep["choices"])
    # forcing the Pallas arm on an unpacked-ineligible b is ignored,
    # not crashed into — same contract as encode — and the record
    # names the arm that ran
    got = dispatch.choose("hamming_topk",
                          {"b": 3, "k": 32, "rows": 50, "width": 12},
                          impl="pallas")
    assert got == "xla"
    assert dispatch.dispatch_report()["choices"][
        "hamming_topk|b=3,k=32,rows=64,width=16"] == "xla"


def test_index_recall_tracks_brute_force_resemblance():
    # queries are token-space near-duplicates; the banded index must
    # put the perturbed source in the top-3 of nearly every query
    from repro.core.schemes import make_scheme
    from repro.data.packing import pad_rows

    rng = np.random.default_rng(4)
    k, b, r = 64, 4, 2
    docs = [np.unique(rng.choice(1 << 20, size=200, replace=False))
            for _ in range(48)]
    scheme = make_scheme("oph", k=k, seed=3)
    idx_rows, nnz = pad_rows(docs, pad_to_multiple=1)
    packed, _ = scheme.encode_packed_numpy(idx_rows, nnz, b)
    index = BandedLSHIndex(k=k, b=b, rows_per_band=r)
    index.insert(list(range(len(docs))), packed)

    found = 0
    n_q = 16
    for qi in range(n_q):
        keep = rng.random(docs[qi].size) > 0.08      # ~8% token churn
        q_doc = docs[qi][keep]
        qi_rows, q_nnz = pad_rows([q_doc], pad_to_multiple=1)
        q_packed, _ = scheme.encode_packed_numpy(qi_rows, q_nnz, b)
        ids, _ = index.query(q_packed[0], top_k=3)
        found += qi in ids
    assert found >= n_q - 2
