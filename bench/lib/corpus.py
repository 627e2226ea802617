"""The benchmark's corpus generator: a planted-topic model with the statistics
of the paper's collections, then the paper's preprocessing (term counts →
TF-IDF → cull to the top-ranked terms → unit rows).

A copy, in NumPy alone, of ``repro.data.synth_corpus.make_corpus`` and
``prepared_corpus`` (with the ``repro.sparse`` steps they call), so that the
yardstick does not move when the program's generator does. For one spec and
seed it gives exactly the rows the program's generator gives
(``bench/tests/test_corpus.py``).
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class CorpusSpec:
    name: str
    n_docs: int
    n_labels: int
    vocab: int            # raw vocabulary before culling
    culled_vocab: int     # the paper culls to 8,000 terms
    mean_doc_len: float   # tokens per doc (before counting repeats)
    topic_terms: int      # terms owned by each label's topic
    topic_weight: float   # P(token from the topic) against the background
    label_zipf: float     # power-law exponent of the label sizes


@dataclasses.dataclass(frozen=True)
class Csr:
    """Rows of a sparse matrix: ``indptr[i]:indptr[i+1]`` delimits row i."""
    data: np.ndarray      # f32[nnz]
    indices: np.ndarray   # i32[nnz] column ids, ascending within a row
    indptr: np.ndarray    # i32[n_rows + 1]
    n_cols: int

    @property
    def n_rows(self) -> int:
        return self.indptr.shape[0] - 1

    def row_ids(self) -> np.ndarray:
        return np.repeat(np.arange(self.n_rows), np.diff(self.indptr))

    def dense(self, rows=None, dtype=np.float32) -> np.ndarray:
        """Dense copies of ``rows`` (default all), in ``dtype``."""
        rows = np.arange(self.n_rows) if rows is None else np.asarray(rows)
        out = np.zeros((rows.size, self.n_cols), dtype)
        lo, hi = self.indptr[rows], self.indptr[rows + 1]
        lens = hi - lo
        r = np.repeat(np.arange(rows.size), lens)
        pos = np.repeat(lo - np.cumsum(lens) + lens, lens) + np.arange(lens.sum())
        out[r, self.indices[pos]] = self.data[pos]
        return out

    def take(self, rows) -> "Csr":
        """The sub-matrix of ``rows``, in that order."""
        rows = np.asarray(rows)
        lo, hi = self.indptr[rows], self.indptr[rows + 1]
        lens = hi - lo
        pos = np.repeat(lo - np.cumsum(lens) + lens, lens) + np.arange(lens.sum())
        indptr = np.zeros(rows.size + 1, np.int32)
        np.cumsum(lens, out=indptr[1:])
        return Csr(self.data[pos], self.indices[pos], indptr, self.n_cols)


def _zipf_probs(v: int, s: float = 1.05) -> np.ndarray:
    p = 1.0 / np.power(np.arange(1, v + 1, dtype=np.float64), s)
    return p / p.sum()


def make_corpus(spec: CorpusSpec, seed: int) -> tuple[Csr, np.ndarray]:
    """(term-count CSR [n_docs, vocab], labels i32[n_docs]): label sizes follow
    a power law; each label owns a topic over a mid-frequency band of the
    vocabulary, mixed with a Zipfian background; document lengths are
    lognormal."""
    rng = np.random.default_rng(seed)
    raw = 1.0 / np.power(np.arange(1, spec.n_labels + 1, dtype=np.float64), spec.label_zipf)
    sizes = np.maximum((raw / raw.sum() * spec.n_docs).astype(np.int64), 1)
    sizes[0] += spec.n_docs - sizes.sum()
    labels = np.repeat(np.arange(spec.n_labels, dtype=np.int32), sizes)
    rng.shuffle(labels)

    background = _zipf_probs(spec.vocab)
    band = np.arange(spec.vocab // 50, spec.vocab)
    doc_lens = np.maximum(
        rng.lognormal(np.log(spec.mean_doc_len), 0.4, spec.n_docs).astype(np.int64), 8
    )
    rows_parts, cols_parts, vals_parts = [], [], []
    for lbl in range(spec.n_labels):
        docs = np.nonzero(labels == lbl)[0]
        if docs.size == 0:
            continue
        topic_ids = rng.choice(band, size=spec.topic_terms, replace=False)
        topic_p = rng.dirichlet(np.full(spec.topic_terms, 0.5))
        lens = doc_lens[docs]
        total = int(lens.sum())
        from_topic = rng.random(total) < spec.topic_weight
        n_topic = int(from_topic.sum())
        toks = np.empty(total, dtype=np.int64)
        toks[from_topic] = topic_ids[rng.choice(spec.topic_terms, size=n_topic, p=topic_p)]
        toks[~from_topic] = rng.choice(spec.vocab, size=total - n_topic, p=background)
        key = np.repeat(docs, lens).astype(np.int64) * spec.vocab + toks
        uniq, counts = np.unique(key, return_counts=True)
        rows_parts.append((uniq // spec.vocab).astype(np.int64))
        cols_parts.append((uniq % spec.vocab).astype(np.int32))
        vals_parts.append(counts.astype(np.float32))

    rows = np.concatenate(rows_parts)
    cols = np.concatenate(cols_parts)
    vals = np.concatenate(vals_parts)
    order = np.lexsort((cols, rows))
    indptr = np.zeros(spec.n_docs + 1, dtype=np.int32)
    np.cumsum(np.bincount(rows, minlength=spec.n_docs), out=indptr[1:])
    return Csr(vals[order], cols[order], indptr, spec.vocab), labels


def tfidf_weight(counts: Csr) -> Csr:
    """tf × smoothed idf: tf is the raw count, idf = log((1+N)/(1+df)) + 1."""
    data = counts.data.astype(np.float64)
    df = np.bincount(counts.indices, minlength=counts.n_cols).astype(np.float64)
    idf = np.log((1.0 + counts.n_rows) / (1.0 + df)) + 1.0
    return dataclasses.replace(
        counts, data=(data * idf[counts.indices]).astype(np.float32))


def cull_terms(weighted: Csr, n_keep: int) -> Csr:
    """Keep the ``n_keep`` terms of highest rank (the sum of a term's weights
    over the corpus, paper §1), re-indexed in their original order."""
    ranks = np.bincount(weighted.indices, weights=weighted.data.astype(np.float64),
                        minlength=weighted.n_cols)
    n_keep = min(n_keep, weighted.n_cols)
    keep = np.sort(np.argpartition(-ranks, n_keep - 1)[:n_keep])
    remap = -np.ones(weighted.n_cols, dtype=np.int32)
    remap[keep] = np.arange(keep.shape[0], dtype=np.int32)
    new_cols = remap[weighted.indices]
    mask = new_cols >= 0
    surv = np.bincount(weighted.row_ids()[mask], minlength=weighted.n_rows)
    indptr = np.zeros(weighted.n_rows + 1, dtype=np.int32)
    np.cumsum(surv, out=indptr[1:])
    return Csr(weighted.data[mask], new_cols[mask], indptr, int(keep.shape[0]))


def unit_rows(m: Csr) -> Csr:
    """L2-normalise every row. The squared norms are summed in float32 in
    element order, as a scatter-add sums them."""
    rows = m.row_ids()
    sq = np.zeros(m.n_rows, np.float32)
    np.add.at(sq, rows, m.data * m.data)
    norms = np.sqrt(np.maximum(sq, np.float32(1e-12)))
    return dataclasses.replace(m, data=m.data / norms[rows])


def prepared_corpus(spec: CorpusSpec, seed: int) -> tuple[Csr, np.ndarray]:
    """counts → TF-IDF → cull to ``spec.culled_vocab`` terms → unit rows.
    Returns (culled unit-row CSR, labels)."""
    counts, labels = make_corpus(spec, seed)
    return unit_rows(cull_terms(tfidf_weight(counts), spec.culled_vocab)), labels


def spec_from_config(cfg: dict, n_docs: int | None = None) -> CorpusSpec:
    """The corpus spec a configuration file states, optionally at another
    document count."""
    return CorpusSpec(**cfg["corpus"], n_docs=cfg["n_docs"] if n_docs is None else n_docs)
