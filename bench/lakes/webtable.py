"""Web-table lake with the DWTC statistics of the MATE paper (§7.1, §7.6.4).

A vectorised copy of ``repro.data.synthetic.make_corpus`` with the same
distributions: table heights uniform in [rows_lo, rows_hi), power-law
widths (``w ** -width_alpha``) over [cols_lo, cols_hi], a value pool sized
for ``avg_pl_length`` posting items per value, and cells drawn from a Zipf
head (``zipf_a``, a ``head_frac`` share of cells) over a uniform body.  The
values mix syllable words, letter strings, numbers and codes as the
original's ``_random_word`` does.

The table shapes are drawn once from ``shape_seed`` and only their order
follows the lake's seed, so every seed indexes the same number of rows and
cells.  Only the table heights, widths and value head are the repo's own
calibration (``SyntheticSpec``); the posting-list length is the paper's.
"""

from __future__ import annotations

import numpy as np

from bench.lake import Lake, rng as seeded

SYLLABLES = np.array([
    "ka", "ro", "mi", "ta", "shi", "lo", "ber", "lin", "mun", "ich", "to",
    "kyo", "am", "ster", "dam", "bo", "ston", "cam", "bridge", "ox", "ford",
    "han", "over", "sto", "ck", "holm", "war", "saw", "pra", "gue", "vien",
    "na", "del", "hi", "se", "oul", "qui", "to", "li", "ma", "ac", "cra",
])
LETTERS = np.array(list("abcdefghijklmnopqrstuvwxyz"))
# English unigram frequencies: rare letters must occur, XASH keys on them
LETTER_P = np.array([
    8.17, 1.49, 2.78, 4.25, 12.7, 2.23, 2.02, 6.09, 6.97, 0.15, 0.77, 4.03,
    2.41, 6.75, 7.51, 1.93, 0.10, 5.99, 6.33, 9.06, 2.76, 0.98, 2.36, 0.15,
    1.97, 0.07,
])
LETTER_P = LETTER_P / LETTER_P.sum()

# the parameters that cut the lake to a size a CPU test can hold
TINY = {"n_tables": 120}


def _join(parts: np.ndarray, lengths: np.ndarray) -> list[str]:
    return ["".join(row[:n]) for row, n in zip(parts.tolist(), lengths.tolist())]


def words(rng: np.random.Generator, n: int) -> list[str]:
    """``n`` values with ``_random_word``'s mix of kinds and lengths."""
    kind = rng.random(n)
    out = np.empty(n, dtype=object)

    sel = np.flatnonzero(kind < 0.45)  # syllable words
    m = sel.size
    w = _join(SYLLABLES[rng.integers(0, SYLLABLES.size, (m, 4))], rng.integers(1, 5, m))
    tail = rng.random(m) < 0.2
    extra = SYLLABLES[rng.integers(0, SYLLABLES.size, m)]
    out[sel] = [a + " " + b if t else a for a, b, t in zip(w, extra.tolist(), tail.tolist())]

    sel = np.flatnonzero((kind >= 0.45) & (kind < 0.75))  # letter strings
    m = sel.size
    lens = rng.integers(3, 20, m)
    w = _join(rng.choice(LETTERS, p=LETTER_P, size=(m, 19)), lens)
    cut = (rng.random(m) * (lens - 1)).astype(np.int64) + 1
    split = rng.random(m) < 0.3
    out[sel] = [
        s[:c] + " " + s[c:] if sp else s
        for s, c, sp in zip(w, cut.tolist(), split.tolist())
    ]

    sel = np.flatnonzero((kind >= 0.75) & (kind < 0.9))  # numbers and codes
    m = sel.size
    hi = 10 ** rng.integers(2, 9, m)
    num = (rng.random(m) * hi).astype(np.int64)
    code = rng.random(m) < 0.3
    pre = _join(LETTERS[rng.integers(0, 26, (m, 2))], np.full(m, 2))
    out[sel] = [
        p + str(v) if c else str(v)
        for v, p, c in zip(num.tolist(), pre, code.tolist())
    ]

    sel = np.flatnonzero(kind >= 0.9)  # long composites
    m = sel.size
    head = _join(SYLLABLES[rng.integers(0, SYLLABLES.size, (m, 2))], np.full(m, 2))
    body = _join(rng.choice(LETTERS, p=LETTER_P, size=(m, 11)), rng.integers(4, 12, m))
    out[sel] = [a + " " + b for a, b in zip(head, body)]

    suffix = rng.random(n) < 0.1
    nums = rng.integers(0, 10_000, n)
    return [
        v + str(s) if f else v
        for v, s, f in zip(out.tolist(), nums.tolist(), suffix.tolist())
    ]


def shapes(params: dict) -> np.ndarray:
    """int64[n_tables, 2] (rows, cols), drawn from ``shape_seed`` alone."""
    rng = np.random.default_rng(params["shape_seed"])
    n = params["n_tables"]
    rows = rng.integers(params["rows_lo"], params["rows_hi"], n)
    widths = np.arange(params["cols_lo"], params["cols_hi"] + 1)
    p = widths.astype(np.float64) ** -params["width_alpha"]
    cols = rng.choice(widths, p=p / p.sum(), size=n)
    return np.stack([rows, cols], axis=1)


def generate(params: dict, seed: int) -> Lake:
    rng = seeded(seed, 1)
    shp = shapes(params)[rng.permutation(params["n_tables"])]
    total_cells = int((shp[:, 0] * shp[:, 1]).sum())
    pool_size = max(int(total_cells / params["avg_pl_length"]), 50)
    pool: dict[str, None] = {}
    while len(pool) < pool_size:
        pool.update(dict.fromkeys(words(rng, pool_size - len(pool) + 64)))
    vocab = np.empty(pool_size, dtype=object)
    vocab[:] = list(pool)[:pool_size]

    head = (rng.zipf(params["zipf_a"], total_cells) - 1) % pool_size
    body = rng.integers(0, pool_size, total_cells)
    ids = np.where(rng.random(total_cells) < params["head_frac"], head, body)
    ids = ids.astype(np.int32)
    tables = []
    at = 0
    for r, c in shp.tolist():
        tables.append(ids[at : at + r * c].reshape(r, c))
        at += r * c
    return Lake(tables=tables, vocab=vocab)
