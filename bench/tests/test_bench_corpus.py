"""The benchmark's copy of the corpus generator gives exactly the rows the
program's generator gives."""
import dataclasses

import numpy as np
import pytest

from lib import corpus


@pytest.mark.parametrize("spec_name,n_docs,seed", [
    ("INEX_LIKE", 1500, 7), ("RCV1_LIKE", 2500, 2**31 + 11)])
def test_prepared_corpus_equals_program(spec_name, n_docs, seed):
    from repro.data import synth_corpus

    spec = dataclasses.replace(getattr(synth_corpus, spec_name), n_docs=n_docs)
    want, want_labels = synth_corpus.prepared_corpus(spec, seed=seed)
    got, labels = corpus.prepared_corpus(corpus.CorpusSpec(**dataclasses.asdict(spec)), seed)
    assert got.n_cols == want.n_cols == spec.culled_vocab
    assert np.array_equal(got.indptr, np.asarray(want.indptr))
    assert np.array_equal(got.indices, np.asarray(want.indices))
    assert got.data.dtype == np.float32
    assert np.array_equal(got.data, np.asarray(want.data))
    assert np.array_equal(labels, want_labels)


def test_dense_and_take_match_program_densify():
    from repro.sparse.csr import Csr, csr_to_dense

    spec = corpus.CorpusSpec("t", 300, 5, 3000, 500, 40.0, 50, 0.5, 1.1)
    m, _ = corpus.prepared_corpus(spec, 3)
    import jax.numpy as jnp

    full = np.asarray(csr_to_dense(Csr(jnp.asarray(m.data), jnp.asarray(m.indices),
                                       jnp.asarray(m.indptr), m.n_cols)))
    rows = np.array([17, 0, 299, 17])
    assert np.array_equal(m.dense(rows), full[rows])
    assert np.array_equal(m.take(rows).dense(), full[rows])
    assert np.array_equal(m.dense(rows, np.float64), full[rows].astype(np.float64))
