"""bench/reference.py and bench/corpus.py against the library, at tiny
sizes on the CPU: the references follow the program's semantics."""
import numpy as np
import pytest

import bench_small  # noqa: F401
from bench import corpus, reference


def _docs():
    rng = np.random.default_rng(0)
    return [corpus.doc_ids(rng, n) for n in (1, 2, 40, 300, 3000)]


@pytest.mark.parametrize("scheme", ["minwise", "oph"])
@pytest.mark.parametrize("b", [1, 6, 8])
def test_hash_reference_matches_numpy_encoder(scheme, b):
    from repro.core.schemes import make_scheme
    from repro.data.packing import pad_rows
    k = 64
    sch = make_scheme(scheme, k, 5)
    encode = {"minwise": reference.minwise_packed,
              "oph": reference.oph_packed}[scheme]
    for d in _docs():
        idx, nnz = pad_rows([d], pad_to_multiple=1)
        want = sch.encode_packed_numpy(idx, nnz, b)[0]
        assert np.array_equal(encode([d], k, b, 5), want), len(d)


def test_pack_roundtrip_matches_library():
    from repro.core.bbit import pack_codes
    codes = np.random.default_rng(1).integers(0, 64, (9, 20)).astype(
        np.uint16)
    assert np.array_equal(reference.pack_codes(codes, 6),
                          pack_codes(codes, 6))
    assert np.array_equal(
        reference.unpack_codes(reference.pack_codes(codes, 6), 20, 6),
        codes)


def test_scores_match_library_logits():
    import jax.numpy as jnp
    from repro.kernels import ref
    rng = np.random.default_rng(2)
    table = rng.standard_normal((16, 256, 1)).astype(np.float32)
    bias = np.float32([0.25])
    codes = rng.integers(0, 256, (7, 16))
    packed = reference.pack_codes(codes.astype(np.uint16), 8)
    want = np.asarray(ref.bbit_linear_packed_fwd(
        jnp.asarray(packed), jnp.asarray(table), 16, 8))[:, 0] + bias[0]
    np.testing.assert_allclose(reference.scores(table, bias, codes), want,
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("world", [None, 2])
def test_training_reference_replays_fit_streaming(tmp_path, world):
    import jax
    from repro.data.hashed_dataset import HashedShardWriter
    from repro.models.linear import BBitLinearConfig
    from repro.train.streaming import fit_streaming
    n, k, b, shards, batch = 1500, 16, 8, 4, 128
    codes = corpus.random_codes(4, n, k, b)
    labels = corpus.planted_labels(4, codes, b, 0.5)
    root = str(tmp_path / "arch")
    w = HashedShardWriter(root, k, b, n_total=n, n_shards=shards)
    w.append(np.arange(n), reference.pack_codes(codes, b), labels)
    w.close()
    got = fit_streaming(root, BBitLinearConfig(k=k, b=b), batch_size=batch,
                        seed=3, data_parallel=world, elastic=True)
    want = reference.run_job(codes, labels, k=k, b=b, shards=shards,
                             batch=batch, seed=3, lr=1e-2, l2=1e-6,
                             avg_start_frac=0.5, world=world or 1)
    assert got.n_steps == want["steps"]
    assert got.examples_seen == want["seen"]
    assert round(got.progressive_acc * got.examples_seen) == want["hits"]
    init = {"table": np.asarray(0.01 * jax.random.normal(
        jax.random.key(3), (k, 256, 1))), "bias": np.zeros(1, np.float32)}
    host = {key: np.asarray(v) for key, v in got.params.items()}
    gaps = reference.change_gaps(host, want["params"], init,
                                 want["first_grad"])
    assert max(gaps.values()) < 1e-6
    for key in host:
        np.testing.assert_allclose(host[key], want["params"][key],
                                   atol=1e-5)


def test_lengths_keep_the_papers_median_and_mean():
    assert corpus.fit_sigma(3051, 12062, 1 << 18) == pytest.approx(1.71901,
                                                                   abs=1e-5)
    lengths = corpus.length_set(65536, 3051, 12062, 1 << 18)
    assert np.median(lengths) == 3051
    assert abs(lengths.mean() - 12062) < 1.0
    assert lengths.max() == 1 << 18 and lengths.min() >= 1
    # a seed changes the order, never the set
    a, b = (corpus.shuffled(lengths, s, 1) for s in (1, 2 ** 33 + 5))
    assert not np.array_equal(a, b)
    assert np.array_equal(np.sort(a), np.sort(b))


def test_documents_and_arrivals():
    d = corpus.serve_doc(2 ** 31 + 9, 4, 5000)
    assert len(np.unique(d)) == 5000
    assert d.min() >= 0 and d.max() < corpus.ID_SPACE
    assert np.array_equal(d, corpus.serve_doc(2 ** 31 + 9, 4, 5000))
    off = corpus.arrival_offsets(3, 50.0, 4.0)
    assert len(off) == 200 and off[0] == 0.0 and off[-1] < 4.0
    assert np.all(np.diff(off) > 0)
    text = corpus.ids_json(np.array([0, 9, 10, 123456789, 2 ** 30 - 1]))
    assert text == b"0,9,10,123456789,1073741823"
