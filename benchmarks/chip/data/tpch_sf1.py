"""TPC-H ``lineitem`` at scale factor 1, all 16 columns, made from a seed
with vectorized numpy (TPC-H spec v3.0.1, clauses 4.2.2 and 4.2.3).

- orders: ``O_ORDERKEY`` sparse as the spec makes it (the first 8 of every
  32 keys), dates uniform in [STARTDATE, ENDDATE - 151 days]; each order has
  1-7 lines (uniform), and the last order is cut so that the table holds
  exactly ``rows`` lines on every seed; ``l_linenumber`` counts 1, 2, ...
  within an order;
- ``l_quantity`` uniform 1-50; ``l_partkey`` uniform over the part table,
  ``l_suppkey`` one of the part's four suppliers,
  ``l_extendedprice`` = quantity x P_RETAILPRICE(partkey), where
  P_RETAILPRICE = (90000 + ((partkey / 10) mod 20001) + 100 (partkey mod 1000)) / 100;
- ``l_discount`` uniform 0.00-0.10 and ``l_tax`` 0.00-0.08, in steps of 0.01;
- ``l_shipdate`` = order date + 1-121 days, ``l_commitdate`` = order date +
  30-90 days, ``l_receiptdate`` = ship date + 1-30 days;
- ``l_returnflag`` 'R' or 'A' (even odds) where the receipt date is on or
  before CURRENTDATE, else 'N'; ``l_linestatus`` 'O' where the ship date is
  after CURRENTDATE, else 'F';
- ``l_shipinstruct`` and ``l_shipmode`` uniform over the spec's lists, and
  ``l_comment`` a text of 10-43 characters at a random offset of a text pool
  made from the spec's grammar words.

Decimals are float32, flags int8 ASCII codes, keys and line numbers int32,
dates int32 days since 1970-01-01, and text fixed-width bytes padded with
NULs, written as variable-width strings (see the configuration's
``assumed``).
"""

from __future__ import annotations

import numpy as np

INSTRUCTIONS = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
# words of the spec's text grammar (clause 4.2.2.14): nouns, verbs, adjectives, adverbs, prepositions
WORDS = (
    "foxes ideas theodolites pinto beans instructions dependencies excuses platelets asymptotes courts "
    "dolphins multipliers sauternes warthogs frets dinos attainments somas Tiresias patterns forges braids "
    "hockey players frays warhorses dugouts notornis epitaphs pearls tithes waters orbits gifts sheaves "
    "depths sentiments decoys realms pains grouches escapades sleep wake are cajole haggle nag use boost "
    "affix detect integrate maintain nod was lose sublate solve thrash promise engage hinder print x-ray "
    "breach eat grow impress mold poach serve run dazzle snooze doze unwind kindle play hang believe doubt "
    "furious sly careful blithe quick fluffy slow quiet ruthless thin close dogged daring brave stealthy "
    "permanent enticing idle busy regular final ironic even bold silent sometimes always never furiously "
    "slyly carefully blithely quickly fluffily slowly quietly ruthlessly thinly closely doggedly daringly "
    "bravely stealthily permanently enticingly idly busily regularly finally ironically evenly boldly "
    "silently about above according to across after against along alongside of among around at atop "
    "before behind beneath beside besides between beyond by despite during except for from in place of "
    "inside instead of into near of on outside over past since through throughout to toward under until "
    "up upon without with within"
).split()
COMMENT_LEN = (10, 43)
POOL_BYTES = 1 << 20


def _days(iso: str) -> int:
    return int(np.datetime64(iso, "D").astype(np.int64))


def part_sizes(rows: int, parts: int) -> list:
    return [rows // parts + (1 if j < rows % parts else 0) for j in range(parts)]


def _choice_text(rng, words: list, n: int) -> np.ndarray:
    return np.asarray(words, dtype=f"S{max(map(len, words))}")[rng.integers(0, len(words), size=n)]


def _comments(rng, n: int) -> np.ndarray:
    """``n`` texts of 10-43 characters, each at a random offset of a pool of
    grammar words joined by spaces, as NUL-padded fixed-width bytes."""
    lo, hi = COMMENT_LEN
    words = np.asarray(WORDS, dtype=object)[rng.integers(0, len(WORDS), size=POOL_BYTES // 6)]
    pool = np.frombuffer(" ".join(words).encode()[:POOL_BYTES], np.uint8)
    windows = np.lib.stride_tricks.sliding_window_view(pool, hi)
    lens = rng.integers(lo, hi + 1, size=n)
    text = windows[rng.integers(0, windows.shape[0], size=n)]  # a copy: (n, hi) uint8
    text[np.arange(hi)[None, :] >= lens[:, None]] = 0
    return text.view(f"S{hi}").reshape(n)


def make(config: dict, seed: int) -> dict:
    rows = int(config["rows"])
    rng = np.random.default_rng([seed, 0x7C41])
    start, end, current = (_days(config["dates"][k]) for k in ("start", "end", "current"))

    lines = rng.integers(1, 8, size=rows // 4 + rows // 8, dtype=np.int64)
    n_orders = int(np.searchsorted(np.cumsum(lines), rows)) + 1
    lines = lines[:n_orders]
    order_date = rng.integers(start, end - 151 + 1, size=n_orders, dtype=np.int32)
    l_orderdate = np.repeat(order_date, lines)[:rows]
    ordinal = np.arange(n_orders, dtype=np.int64)
    orderkey = np.repeat((ordinal // 8) * 32 + ordinal % 8 + 1, lines)[:rows].astype(np.int32)
    first_line = np.repeat(np.cumsum(lines) - lines, lines)[:rows]
    linenumber = (np.arange(rows) - first_line + 1).astype(np.int32)

    quantity = rng.integers(1, 51, size=rows, dtype=np.int64)
    part_count = int(config["part_count"])
    supp_count = int(config["supplier_count"])
    partkey = rng.integers(1, part_count + 1, size=rows, dtype=np.int64)
    supp_i = rng.integers(0, 4, size=rows, dtype=np.int64)
    suppkey = (partkey + supp_i * (supp_count // 4 + (partkey - 1) // supp_count)) % supp_count + 1
    retail_cents = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
    extendedprice = (quantity * retail_cents / 100.0).astype(np.float32)
    discount = (rng.integers(0, 11, size=rows) / 100.0).astype(np.float32)
    tax = (rng.integers(0, 9, size=rows) / 100.0).astype(np.float32)
    shipdate = (l_orderdate + rng.integers(1, 122, size=rows, dtype=np.int32)).astype(np.int32)
    commitdate = (l_orderdate + rng.integers(30, 91, size=rows, dtype=np.int32)).astype(np.int32)
    receiptdate = (shipdate + rng.integers(1, 31, size=rows, dtype=np.int32)).astype(np.int32)
    returned = np.where(rng.integers(0, 2, size=rows) == 1, ord("R"), ord("A"))
    returnflag = np.where(receiptdate <= current, returned, ord("N")).astype(np.int8)
    linestatus = np.where(shipdate > current, ord("O"), ord("F")).astype(np.int8)

    columns = {
        "l_orderkey": orderkey,
        "l_partkey": partkey.astype(np.int32),
        "l_suppkey": suppkey.astype(np.int32),
        "l_linenumber": linenumber,
        "l_quantity": quantity.astype(np.float32),
        "l_extendedprice": extendedprice,
        "l_discount": discount,
        "l_tax": tax,
        "l_returnflag": returnflag,
        "l_linestatus": linestatus,
        "l_shipdate": shipdate,
        "l_commitdate": commitdate,
        "l_receiptdate": receiptdate,
        "l_shipinstruct": _choice_text(rng, INSTRUCTIONS, rows),
        "l_shipmode": _choice_text(rng, MODES, rows),
        "l_comment": _comments(rng, rows),
    }
    return {"lineitem": {"columns": columns, "parts": part_sizes(rows, int(config["parts"]))}}
