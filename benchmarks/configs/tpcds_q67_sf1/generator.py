"""Seeded tables for TPC-DS query 67 at scale factor 1, as numpy arrays.

``date_dim`` is ``tpcds_q51_sf1``'s calendar (``d_date_sk`` 2415022 is
1900-01-02, one row a day for 73,049 days, ``d_month_seq`` the months since
January 1900) with ``d_year``, ``d_qoy`` and ``d_moy`` computed from the
day. ``store_sales`` keeps q51's shapes where they meet: sold dates uniform
over the five years of sales, items uniform over ``item``'s 18,000 keys;
new here are a store uniform over ``store``'s 12 keys, ``ss_quantity``
uniform over 1..100 and ``ss_sales_price`` as the unscaled int64 of
``decimal(7,2)`` uniform over dsdgen's 0.00 .. 200.00.

``item``'s four strings follow the specification's distributions as far as
they are written down here (``config.json`` lists each as assumed): ten
categories, the classes of each, a brand as two of dsdgen's brand
syllables (one for the category, one for the class) and `` #n``, and a
product name spelled from the item key's digits in dsdgen's syllables.
As dsdgen's ``nullCheck`` does, a share of item rows takes a null bitmap,
and each of the four strings of such a row is null with probability one
half. ``s_store_id`` is dsdgen's 16-character business key; the store
rows are revisions of a slowly changing dimension, as dsdgen's
``setSCDKeys`` numbers them, so 12 rows hold 6 keys. Imports nothing of
the engine.
"""

import numpy as np

FIRST_DATE_SK = 2415022                  # 1900-01-02
FIRST_DATE = np.datetime64("1900-01-02")
SOLD_FROM_SK, SOLD_TO_SK = 2450816, 2452642   # 1998-01-02 .. 2003-01-02
ITEMS, STORES = 18_000, 12
BRANDS_PER_CLASS = 10
NULL_BITMAP_SHARE = 0.005    # item rows that take a null bitmap
STRINGS = ("i_category", "i_class", "i_brand", "i_product_name")

CLASSES = {
    "Books": ["arts", "business", "computers", "cooking", "entertainments",
              "fiction", "history", "home repair", "mystery", "parenting",
              "reference", "romance", "science", "self-help", "sports",
              "travel"],
    "Children": ["infants", "newborn", "school-uniforms", "toddlers"],
    "Electronics": ["audio", "automotive", "camcorders", "cameras",
                    "disk drives", "dvd/vcr players", "karoke", "memory",
                    "monitors", "musical", "personal", "portable",
                    "scanners", "stereo", "televisions", "wireless"],
    "Home": ["accent", "bathroom", "bedding", "blinds/shades",
             "curtains/drapes", "decor", "flatware", "furniture",
             "glassware", "kids", "lighting", "mattresses", "paint", "rugs",
             "tables", "wallpaper"],
    "Jewelry": ["birdal", "bracelets", "consignment", "costume", "custom",
                "diamonds", "earings", "estate", "gold", "jewelry boxes",
                "loose stones", "mens watch", "pendants", "rings",
                "semi-precious", "womens watch"],
    "Men": ["accessories", "pants", "shirts", "sports-apparel"],
    "Music": ["classical", "country", "pop", "rock"],
    "Shoes": ["athletic", "kids", "mens", "womens"],
    "Sports": ["archery", "athletic shoes", "baseball", "basketball",
               "camping", "fishing", "fitness", "football", "golf", "guns",
               "hockey", "optics", "outdoor", "pools", "sailing", "tennis"],
    "Women": ["dresses", "fragrances", "maternity", "swimwear"],
}
BRAND_SYLLABLES = ["amalg", "edu pack", "exporti", "importo", "scholar",
                   "univ", "corp", "brand", "maxi", "nameless"]
DIGIT_SYLLABLES = ["bar", "ought", "able", "pri", "ese", "anti", "cally",
                   "ation", "eing", "n st"]


def calendar(n_date: int) -> dict:
    days = FIRST_DATE + np.arange(n_date)
    months = days.astype("datetime64[M]").astype(np.int64)   # since 1970-01
    years = days.astype("datetime64[Y]").astype(np.int64) + 1970
    moy = (months % 12 + 1).astype(np.int32)
    return {
        "d_date_sk": FIRST_DATE_SK + np.arange(n_date),
        "d_month_seq": (months + 70 * 12).astype(np.int32),
        "d_year": years.astype(np.int32),
        "d_qoy": ((moy - 1) // 3 + 1).astype(np.int32),
        "d_moy": moy,
    }


def product_name(sk: int) -> str:
    return "".join(DIGIT_SYLLABLES[int(d)] for d in str(sk))


def business_key(sk: int) -> int:
    """The row that first holds store row ``sk``'s business key: dsdgen's
    slowly changing dimension runs in blocks of six rows, a key with one
    revision, one with two and one with three."""
    block, at = divmod(sk - 1, 6)
    return 6 * block + (1, 2, 2, 4, 4, 4)[at]


def store_id(sk: int) -> str:
    """dsdgen's business key of row ``sk``: eight ``A``, then the number
    in base 16, least significant digit first, in the letters ``A`` ..
    ``P``."""
    digits = []
    for _ in range(8):
        digits.append(chr(ord("A") + sk % 16))
        sk //= 16
    return "A" * 8 + "".join(digits)


def items(rng, n: int) -> dict:
    cats = sorted(CLASSES)
    cat = rng.integers(0, len(cats), n)
    counts = np.array([len(CLASSES[c]) for c in cats])
    cls = (rng.random(n) * counts[cat]).astype(np.int64)
    brand = rng.integers(1, BRANDS_PER_CLASS + 1, n)
    sk = np.arange(1, n + 1)
    out = {
        "i_item_sk": sk,
        "i_category": np.array([cats[c] for c in cat], dtype=object),
        "i_class": np.array([CLASSES[cats[c]][k] for c, k in zip(cat, cls)],
                            dtype=object),
        "i_brand": np.array(
            [f"{BRAND_SYLLABLES[c]}{BRAND_SYLLABLES[k % 10]} #{b}"
             for c, k, b in zip(cat, cls, brand)], dtype=object),
        "i_product_name": np.array([product_name(int(s)) for s in sk],
                                   dtype=object),
    }
    bitmap = rng.random(n) < NULL_BITMAP_SHARE
    for k in STRINGS:
        out[k][bitmap & (rng.random(n) < 0.5)] = None
    return out


def generate(seed: int, rows: dict) -> dict:
    """``rows`` maps table name to row count; returns table -> column -> array."""
    rng = np.random.default_rng(seed)
    n_item, n_store, n = rows["item"], rows["store"], rows["store_sales"]
    item = items(rng, n_item)
    store = {"s_store_sk": np.arange(1, n_store + 1),
             "s_store_id": np.array([store_id(business_key(s)) for s in
                                     range(1, n_store + 1)], dtype=object)}
    store_sales = {
        "ss_sold_date_sk": rng.integers(SOLD_FROM_SK, SOLD_TO_SK + 1, n),
        "ss_item_sk": rng.integers(1, n_item + 1, n),
        "ss_store_sk": rng.integers(1, n_store + 1, n),
        "ss_quantity": rng.integers(1, 101, n).astype(np.int32),
        "ss_sales_price": rng.integers(0, 20_001, n),
    }
    return {"date_dim": calendar(rows["date_dim"]), "store": store,
            "item": item, "store_sales": store_sales}
