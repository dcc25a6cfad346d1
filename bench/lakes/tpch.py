"""A TPC-H warehouse's table exports, generated from the specification.

TPC-H Standard Specification, revision 3: clause 1.4 gives the eight
relations, their primary keys and foreign keys; clause 4.2.3 the rows per
scale factor and each column's domain.  The data is drawn from those
clauses with numpy (dbgen is not used), and written as dbgen's ``.tbl``
files write it: integers in decimal, dates ``YYYY-MM-DD``, money with two
decimals.  Small integers of unrelated columns (keys, line numbers,
quantities, sizes, nation keys) then collide across the lake as they do
in a real warehouse.

The keys follow the specification exactly:

- ``ps_suppkey`` of the i-th supplier of part p is
  ``(p + i * (S // 4 + (p - 1) // S)) % S + 1``, S = SF * 10,000;
- ``l_suppkey`` is one of ``l_partkey``'s four partsupp suppliers;
- ``o_orderkey`` is sparse: the first 8 of every 32 keys;
- ``o_custkey`` never takes a customer key divisible by 3;
- ``l_linenumber`` runs 1..n within its order, n uniform in 1..7.

Columns whose rule needs the specification's word lists or text grammar
(part names and types, containers, comments) are drawn from seeded
vocabularies of the specification's sizes; addresses are its random
v-strings.  The lake's tables are the extracts a warehouse exports:
``orders`` and ``lineitem`` by the month of ``o_orderdate``, ``customer``
and ``supplier`` by nation, ``part`` and ``partsupp`` by brand, ``nation``
and ``region`` whole.  An extract with no rows is not exported.
"""

from __future__ import annotations

import numpy as np

from bench.lake import Lake, factorize, rng as seeded

# the parameters that cut the lake to a size a CPU test can hold
TINY = {"scale_factor": 0.001}

COLUMNS = {
    "part": ["p_partkey", "p_name", "p_mfgr", "p_brand", "p_type", "p_size",
             "p_container", "p_retailprice", "p_comment"],
    "supplier": ["s_suppkey", "s_name", "s_address", "s_nationkey", "s_phone",
                 "s_acctbal", "s_comment"],
    "partsupp": ["ps_partkey", "ps_suppkey", "ps_availqty", "ps_supplycost",
                 "ps_comment"],
    "customer": ["c_custkey", "c_name", "c_address", "c_nationkey", "c_phone",
                 "c_acctbal", "c_mktsegment", "c_comment"],
    "orders": ["o_orderkey", "o_custkey", "o_orderstatus", "o_totalprice",
               "o_orderdate", "o_orderpriority", "o_clerk", "o_shippriority",
               "o_comment"],
    "lineitem": ["l_orderkey", "l_partkey", "l_suppkey", "l_linenumber",
                 "l_quantity", "l_extendedprice", "l_discount", "l_tax",
                 "l_returnflag", "l_linestatus", "l_shipdate", "l_commitdate",
                 "l_receiptdate", "l_shipinstruct", "l_shipmode", "l_comment"],
    "nation": ["n_nationkey", "n_name", "n_regionkey", "n_comment"],
    "region": ["r_regionkey", "r_name", "r_comment"],
}

# clause 4.2.3: nations with their regions, and the regions
NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1), ("EGYPT", 4),
    ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3), ("INDIA", 2), ("INDONESIA", 2),
    ("IRAN", 4), ("IRAQ", 4), ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0),
    ("MOROCCO", 0), ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3), ("UNITED KINGDOM", 3),
    ("UNITED STATES", 1),
]
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
INSTRUCTIONS = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]

START = np.datetime64("1992-01-01")
END = np.datetime64("1998-12-31")
CURRENT = np.datetime64("1995-06-17")

LETTERS = np.array(list("abcdefghijklmnopqrstuvwxyz"))
VCHARS = np.array(list("abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789, "))


def suppkey(partkey: np.ndarray, i: np.ndarray, n_supp: int) -> np.ndarray:
    """Clause 4.2.3: the i-th (0..3) supplier of each part."""
    return (partkey + i * (n_supp // 4 + (partkey - 1) // n_supp)) % n_supp + 1


def order_keys(n: int) -> np.ndarray:
    """Clause 4.2.3: sparse order keys, the first 8 of every 32."""
    i = np.arange(n, dtype=np.int64)
    return (i // 8) * 32 + i % 8 + 1


def _ints(a: np.ndarray) -> np.ndarray:
    return np.array([str(v) for v in a.tolist()], dtype=object)


def _money(cents: np.ndarray) -> np.ndarray:
    return np.array(
        ["%s%d.%02d" % ("-" if c < 0 else "", abs(c) // 100, abs(c) % 100) for c in cents.tolist()],
        dtype=object,
    )


def _dates(days: np.ndarray) -> np.ndarray:
    return np.datetime_as_string(days.astype("datetime64[D]")).astype(object)


def _words(rng: np.random.Generator, n: int, lo: int, hi: int) -> np.ndarray:
    """``n`` distinct lower-case words of ``lo``..``hi`` letters."""
    out: dict[str, None] = {}
    while len(out) < n:
        m = n - len(out) + 8
        chars = LETTERS[rng.integers(0, 26, (m, hi))]
        lens = rng.integers(lo, hi + 1, m)
        out.update(dict.fromkeys("".join(c[:k]) for c, k in zip(chars.tolist(), lens.tolist())))
    return np.array(list(out)[:n], dtype=object)


class _Text:
    """Comments as dbgen makes them, substrings of one pre-generated text
    pool at random offsets; the pool is seeded words, not the grammar."""

    def __init__(self, rng: np.random.Generator, size: int = 1 << 20):
        vocab = _words(rng, 300, 2, 10)
        parts, n = [], 0
        while n < size:
            w = vocab[rng.integers(0, vocab.size, 4096)].tolist()
            parts.append(" ".join(w))
            n += len(parts[-1]) + 1
        self.pool = " ".join(parts)
        self.rng = rng

    def __call__(self, n: int, lo: int, hi: int) -> np.ndarray:
        lens = self.rng.integers(lo, hi + 1, n)
        at = self.rng.integers(0, len(self.pool) - hi, n)
        pool = self.pool
        return np.array([pool[a : a + k].strip() for a, k in zip(at.tolist(), lens.tolist())], dtype=object)


def _vstring(rng: np.random.Generator, n: int, lo: int, hi: int) -> np.ndarray:
    chars = VCHARS[rng.integers(0, VCHARS.size, (n, hi))]
    lens = rng.integers(lo, hi + 1, n)
    return np.array(["".join(c[:k]).strip() for c, k in zip(chars.tolist(), lens.tolist())], dtype=object)


def _phone(rng: np.random.Generator, nationkey: np.ndarray) -> np.ndarray:
    a, b = rng.integers(100, 1000, (2, nationkey.size))
    c = rng.integers(1000, 10000, nationkey.size)
    return np.array(
        [f"{n + 10}-{x}-{y}-{z}" for n, x, y, z in zip(nationkey.tolist(), a.tolist(), b.tolist(), c.tolist())],
        dtype=object,
    )


def _acctbal(rng: np.random.Generator, n: int) -> np.ndarray:
    return _money(rng.integers(-99_999, 999_999 + 1, n))


def _syllables(rng: np.random.Generator, sizes: tuple[int, ...]) -> np.ndarray:
    """Every combination of seeded upper-case syllable lists of ``sizes``."""
    lists = [_words(rng, s, 3, 9) for s in sizes]
    grid = np.array(np.meshgrid(*[np.arange(s) for s in sizes], indexing="ij")).reshape(len(sizes), -1)
    return np.array(
        [" ".join(str(lists[j][g[j]]).upper() for j in range(len(sizes))) for g in grid.T.tolist()],
        dtype=object,
    )


def tables(params: dict, seed: int) -> dict[str, dict[str, np.ndarray]]:
    """Every relation as named columns of integers or strings, in key order."""
    rng = seeded(seed, 1)
    sf = float(params["scale_factor"])
    n_part, n_supp = round(sf * 200_000), round(sf * 10_000)
    n_cust, n_ord = round(sf * 150_000), round(sf * 1_500_000)
    n_clerk = max(1, round(sf * 1_000))
    text = _Text(rng)

    # part and partsupp
    pk = np.arange(1, n_part + 1, dtype=np.int64)
    names = _words(rng, 92, 3, 10)
    name = np.array([" ".join(row) for row in names[np.argsort(rng.random((n_part, names.size)), axis=1)[:, :5]].tolist()],
                    dtype=object)
    mfgr = rng.integers(1, 6, n_part)
    brand = mfgr * 10 + rng.integers(1, 6, n_part)
    price = 90_000 + (pk // 10) % 20_001 + 100 * (pk % 1_000)  # cents
    part = {
        "p_partkey": pk, "p_name": name,
        "p_mfgr": np.array([f"Manufacturer#{m}" for m in mfgr.tolist()], dtype=object),
        "p_brand": np.array([f"Brand#{b}" for b in brand.tolist()], dtype=object),
        "p_type": _syllables(rng, (6, 5, 5))[rng.integers(0, 150, n_part)],
        "p_size": rng.integers(1, 51, n_part),
        "p_container": _syllables(rng, (5, 8))[rng.integers(0, 40, n_part)],
        "p_retailprice": price, "p_comment": text(n_part, 5, 22),
    }
    ps_part = np.repeat(pk, 4)
    ps_i = np.tile(np.arange(4), n_part)
    partsupp = {
        "ps_partkey": ps_part, "ps_suppkey": suppkey(ps_part, ps_i, n_supp),
        "ps_availqty": rng.integers(1, 10_000, ps_part.size),
        "ps_supplycost": rng.integers(100, 100_001, ps_part.size),
        "ps_comment": text(ps_part.size, 49, 198),
    }

    sk = np.arange(1, n_supp + 1, dtype=np.int64)
    s_nation = rng.integers(0, 25, n_supp)
    supplier = {
        "s_suppkey": sk, "s_name": np.array([f"Supplier#{k:09d}" for k in sk.tolist()], dtype=object),
        "s_address": _vstring(rng, n_supp, 10, 40), "s_nationkey": s_nation,
        "s_phone": _phone(rng, s_nation), "s_acctbal": _acctbal(rng, n_supp),
        "s_comment": text(n_supp, 25, 100),
    }

    ck = np.arange(1, n_cust + 1, dtype=np.int64)
    c_nation = rng.integers(0, 25, n_cust)
    customer = {
        "c_custkey": ck, "c_name": np.array([f"Customer#{k:09d}" for k in ck.tolist()], dtype=object),
        "c_address": _vstring(rng, n_cust, 10, 40), "c_nationkey": c_nation,
        "c_phone": _phone(rng, c_nation), "c_acctbal": _acctbal(rng, n_cust),
        "c_mktsegment": np.array(SEGMENTS, dtype=object)[rng.integers(0, 5, n_cust)],
        "c_comment": text(n_cust, 29, 116),
    }

    # orders and their line items
    ok = order_keys(n_ord)
    allowed = ck[ck % 3 != 0]
    odate = START + rng.integers(0, int((END - np.timedelta64(151, "D") - START).astype(int)) + 1, n_ord)
    n_lines = rng.integers(1, 8, n_ord)
    line_order = np.repeat(np.arange(n_ord), n_lines)
    n_li = line_order.size
    first = np.cumsum(n_lines) - n_lines
    linenumber = np.arange(n_li) - np.repeat(first, n_lines) + 1
    l_part = rng.integers(1, n_part + 1, n_li)
    quantity = rng.integers(1, 51, n_li)
    eprice = quantity * price[l_part - 1]
    discount = rng.integers(0, 11, n_li)
    tax = rng.integers(0, 9, n_li)
    ship = odate[line_order] + rng.integers(1, 122, n_li)
    commit = odate[line_order] + rng.integers(30, 91, n_li)
    receipt = ship + rng.integers(1, 31, n_li)
    returned = np.where(rng.random(n_li) < 0.5, "R", "A")
    lineitem = {
        "l_orderkey": ok[line_order], "l_partkey": l_part,
        "l_suppkey": suppkey(l_part, rng.integers(0, 4, n_li), n_supp),
        "l_linenumber": linenumber, "l_quantity": quantity, "l_extendedprice": eprice,
        "l_discount": discount, "l_tax": tax,
        "l_returnflag": np.where(receipt <= CURRENT, returned, "N").astype(object),
        "l_linestatus": np.where(ship > CURRENT, "O", "F").astype(object),
        "l_shipdate": ship, "l_commitdate": commit, "l_receiptdate": receipt,
        "l_shipinstruct": np.array(INSTRUCTIONS, dtype=object)[rng.integers(0, 4, n_li)],
        "l_shipmode": np.array(MODES, dtype=object)[rng.integers(0, 7, n_li)],
        "l_comment": text(n_li, 10, 43),
    }
    # dbgen's integer cents: eprice * (1 - discount) * (1 + tax), summed per order
    charge = eprice * (100 - discount) // 100 * (100 + tax) // 100
    open_lines = np.bincount(line_order, weights=lineitem["l_linestatus"] == "O", minlength=n_ord)
    status = np.where(open_lines == n_lines, "O", np.where(open_lines == 0, "F", "P"))
    orders = {
        "o_orderkey": ok, "o_custkey": allowed[rng.integers(0, allowed.size, n_ord)],
        "o_orderstatus": status.astype(object),
        "o_totalprice": np.bincount(line_order, weights=charge, minlength=n_ord).astype(np.int64),
        "o_orderdate": odate,
        "o_orderpriority": np.array(PRIORITIES, dtype=object)[rng.integers(0, 5, n_ord)],
        "o_clerk": np.array([f"Clerk#{c:09d}" for c in rng.integers(1, n_clerk + 1, n_ord).tolist()], dtype=object),
        "o_shippriority": np.zeros(n_ord, dtype=np.int64),
        "o_comment": text(n_ord, 19, 78),
    }

    nation = {
        "n_nationkey": np.arange(25), "n_name": np.array([n for n, _ in NATIONS], dtype=object),
        "n_regionkey": np.array([r for _, r in NATIONS]), "n_comment": text(25, 31, 114),
    }
    region = {
        "r_regionkey": np.arange(5), "r_name": np.array(REGIONS, dtype=object),
        "r_comment": text(5, 31, 115),
    }
    return {
        "part": part, "supplier": supplier, "partsupp": partsupp, "customer": customer,
        "orders": orders, "lineitem": lineitem, "nation": nation, "region": region,
    }


MONEY = {"p_retailprice", "ps_supplycost", "o_totalprice", "l_extendedprice"}
HUNDREDTHS = {"l_discount", "l_tax"}


def _text(name: str, col: np.ndarray) -> np.ndarray:
    """A column as dbgen's ``.tbl`` output writes it."""
    if col.dtype == object:
        return col
    if col.dtype.kind == "M":
        return _dates(col)
    if name in MONEY:
        return _money(col)
    if name in HUNDREDTHS:
        return np.array([f"0.{v:02d}" for v in col.tolist()], dtype=object)
    return _ints(col)


def extracts(rel: dict[str, dict[str, np.ndarray]]) -> list[tuple[str, np.ndarray]]:
    """(relation, row indices) of each exported table, in a fixed order."""
    month = rel["orders"]["o_orderdate"].astype("datetime64[M]").astype(np.int64)
    order_of_line = np.searchsorted(rel["orders"]["o_orderkey"], rel["lineitem"]["l_orderkey"])
    brand = rel["part"]["p_brand"]
    by = {
        "orders": month,
        "lineitem": month[order_of_line],
        "customer": rel["customer"]["c_nationkey"],
        "supplier": rel["supplier"]["s_nationkey"],
        "part": brand,
        "partsupp": brand[rel["partsupp"]["ps_partkey"] - 1],
        "nation": np.zeros(25, dtype=np.int64),
        "region": np.zeros(5, dtype=np.int64),
    }
    out = []
    for name in COLUMNS:
        group = by[name]
        for g in np.unique(group):
            out.append((name, np.flatnonzero(group == g)))
    return out


def generate(params: dict, seed: int) -> Lake:
    rel = tables(params, seed)
    text = {name: [_text(c, rel[name][c]) for c in COLUMNS[name]] for name in COLUMNS}
    columns, relation = [], []
    for name, rows in extracts(rel):
        columns.append([col[rows] for col in text[name]])
        relation.append(name)
    ids, vocab = factorize(columns)
    return Lake(tables=ids, vocab=vocab, relation=relation, columns=dict(COLUMNS))
