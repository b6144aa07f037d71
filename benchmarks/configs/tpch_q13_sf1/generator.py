"""Seeded SF1-shaped ``customer`` and ``orders``: the 4 columns TPC-H Q13
reads, as numpy arrays, in dbgen's shapes (clause 4.2.3):

- ``c_custkey`` 1 .. customers;
- ``o_orderkey`` sparse, the first 8 of every 32 keys (dbgen's
  ``mk_sparse``); ``o_custkey`` uniform over the customers whose key is no
  multiple of 3, so that a third of the customers have no order. Both are
  ``tpch_q18_sf1``'s draws: the same seed gives the same two columns;
- ``o_comment`` a text string of 19 to 78 characters (clause 4.2.3's
  ``O_COMMENT``, length uniform over 0.4 to 1.6 times 49), cut at a random
  place out of a text pool made by clause 4.2.2's grammar and word lists, as
  dbgen cuts it.

Assumed (``config.json`` says why): the words' and the grammar's weights
below; a pool of ``POOL_BYTES`` in place of dbgen's 300 MB; every comment
distinct (a comment met twice is cut again elsewhere, until none is).
Imports nothing of the engine.
"""

import numpy as np

COMMENT_LO, COMMENT_HI = 19, 78
POOL_BYTES = 8 << 20

# clause 4.2.2's lists, each word with its weight
NOUNS = (
    ("packages", 40), ("requests", 40), ("accounts", 40), ("deposits", 40),
    ("foxes", 20), ("ideas", 20), ("theodolites", 20), ("pinto beans", 20),
    ("instructions", 20), ("dependencies", 10), ("excuses", 10),
    ("platelets", 10), ("asymptotes", 10), ("courts", 5), ("dolphins", 5),
    ("multipliers", 1), ("sauternes", 1), ("warthogs", 1), ("frets", 1),
    ("dinos", 1), ("attainments", 1), ("somas", 1), ("Tiresias", 1),
    ("patterns", 1), ("forges", 1), ("braids", 1), ("hockey players", 1),
    ("frays", 1), ("warhorses", 1), ("dugouts", 1), ("notornis", 1),
    ("epitaphs", 1), ("pearls", 1), ("tithes", 1), ("waters", 1),
    ("orbits", 1), ("gifts", 1), ("sheaves", 1), ("depths", 1),
    ("sentiments", 1), ("decoys", 1), ("realms", 1), ("pains", 1),
    ("grouches", 1), ("escapades", 1))
VERBS = (
    ("sleep", 20), ("wake", 20), ("are", 20), ("cajole", 20), ("haggle", 20),
    ("nag", 10), ("use", 10), ("boost", 10), ("affix", 5), ("detect", 5),
    ("integrate", 5), ("maintain", 1), ("nod", 1), ("was", 1), ("lose", 1),
    ("sublate", 1), ("solve", 1), ("thrash", 1), ("promise", 1),
    ("engage", 1), ("hinder", 1), ("print", 1), ("x-ray", 1), ("breach", 1),
    ("eat", 1), ("grow", 1), ("impress", 1), ("mold", 1), ("poach", 1),
    ("serve", 1), ("run", 1), ("dazzle", 1), ("snooze", 1), ("doze", 1),
    ("unwind", 1), ("kindle", 1), ("play", 1), ("hang", 1), ("believe", 1),
    ("doubt", 1))
ADJECTIVES = (
    ("special", 20), ("pending", 20), ("unusual", 20), ("express", 20),
    ("furious", 1), ("sly", 1), ("careful", 1), ("blithe", 1), ("quick", 1),
    ("fluffy", 1), ("slow", 1), ("quiet", 1), ("ruthless", 1), ("thin", 1),
    ("close", 1), ("dogged", 1), ("daring", 1), ("brave", 1),
    ("stealthy", 1), ("permanent", 1), ("enticing", 1), ("idle", 1),
    ("busy", 1), ("regular", 50), ("final", 40), ("ironic", 40),
    ("even", 30), ("bold", 20), ("silent", 10))
ADVERBS = (
    ("sometimes", 1), ("always", 1), ("never", 1), ("furiously", 50),
    ("slyly", 50), ("carefully", 50), ("blithely", 40), ("quickly", 30),
    ("fluffily", 20), ("slowly", 1), ("quietly", 1), ("ruthlessly", 1),
    ("thinly", 1), ("closely", 1), ("doggedly", 1), ("daringly", 1),
    ("bravely", 1), ("stealthily", 1), ("permanently", 1),
    ("enticingly", 1), ("idly", 1), ("busily", 1), ("regularly", 1),
    ("finally", 1), ("ironically", 1), ("evenly", 1), ("boldly", 1),
    ("silently", 1))
PREPOSITIONS = (
    ("about", 50), ("above", 50), ("according to", 50), ("across", 50),
    ("after", 50), ("against", 40), ("along", 40), ("alongside of", 30),
    ("among", 30), ("around", 20), ("at", 10), ("atop", 1), ("before", 1),
    ("behind", 1), ("beneath", 1), ("beside", 1), ("besides", 1),
    ("between", 1), ("beyond", 1), ("by", 1), ("despite", 1), ("during", 1),
    ("except", 1), ("for", 1), ("from", 1), ("in place of", 1),
    ("inside", 1), ("instead of", 1), ("into", 1), ("near", 1), ("of", 1),
    ("on", 1), ("outside", 1), ("over", 1), ("past", 1), ("since", 1),
    ("through", 1), ("throughout", 1), ("to", 1), ("toward", 1),
    ("under", 1), ("until", 1), ("up", 1), ("upon", 1), ("without", 1),
    ("with", 1), ("within", 1))
AUXILIARIES = (
    ("do", 1), ("may", 1), ("might", 1), ("shall", 1), ("will", 1),
    ("would", 1), ("can", 1), ("could", 1), ("should", 1), ("ought to", 1),
    ("must", 1), ("will have to", 1), ("shall have to", 1),
    ("could have to", 1), ("should have to", 1), ("must have to", 1),
    ("need to", 1), ("try to", 1))
TERMINATORS = ((".", 50), (";", 1), (":", 1), ("?", 1), ("!", 1),
               ("--", 1))
# a sentence of phrases (N noun, V verb, P prepositional, T terminator); a
# phrase of words (n noun, v verb, j adjective, d adverb, p preposition, x
# auxiliary), a word written "j," followed by a comma
SENTENCES = ((("N", "V", "T"), 3), (("N", "V", "P", "T"), 3),
             (("N", "V", "N", "T"), 3), (("N", "P", "V", "N", "T"), 1),
             (("N", "P", "V", "P", "T"), 1))
PHRASES = {
    "N": (((("n",), 10), (("j", "n"), 20), (("j,", "j", "n"), 10),
           (("d", "j", "n"), 50))),
    "V": (((("v",), 30), (("x", "v"), 1), (("v", "d"), 40),
           (("x", "v", "d"), 1))),
    "P": (((("p", "the", "N"), 1),)),
}
LISTS = {"n": NOUNS, "v": VERBS, "j": ADJECTIVES, "d": ADVERBS,
         "p": PREPOSITIONS, "x": AUXILIARIES}


class _Draw:
    """Weighted picks from the lists, drawn in bulk from one generator."""

    def __init__(self, rng, block: int = 1 << 16):
        self._rng, self._block = rng, block
        self._picks = {}

    def __call__(self, choices):
        key = id(choices)
        picks, left = self._picks.get(key, ((), 0))
        if not left:
            weights = np.array([w for _, w in choices], dtype=np.float64)
            at = self._rng.choice(len(choices), self._block,
                                  p=weights / weights.sum())
            picks, left = [choices[i][0] for i in at.tolist()], self._block
        self._picks[key] = (picks, left - 1)
        return picks[self._block - left]


def _words(draw, symbol: str) -> list:
    if symbol in PHRASES:
        return [w for s in draw(PHRASES[symbol]) for w in _words(draw, s)]
    if symbol == "the":
        return ["the"]
    return [draw(LISTS[symbol[0]]) + symbol[1:]]


def text_pool(rng, size: int = POOL_BYTES) -> str:
    """Sentences of the grammar, one space apart, until ``size`` bytes."""
    draw = _Draw(rng)
    pieces, length = [], 0
    while length < size:
        words = []
        for symbol in draw(SENTENCES):
            if symbol == "T":
                words[-1] += draw(TERMINATORS)
            else:
                words += _words(draw, symbol)
        sentence = " ".join(words)
        pieces.append(sentence)
        length += len(sentence) + 1
    return " ".join(pieces)[:size]


def comments(rng, pool: str, n: int) -> np.ndarray:
    """``n`` distinct substrings of ``pool``, of uniform length
    ``COMMENT_LO .. COMMENT_HI``, at uniform places."""
    out = [None] * n
    seen = set()
    todo = np.arange(n)
    while len(todo):
        lengths = rng.integers(COMMENT_LO, COMMENT_HI + 1, len(todo))
        starts = rng.integers(0, len(pool) - lengths + 1)
        again = []
        for i, s, ln in zip(todo.tolist(), starts.tolist(), lengths.tolist()):
            text = pool[s:s + ln]
            if text in seen:
                again.append(i)
            else:
                seen.add(text)
                out[i] = text
        todo = np.array(again, dtype=np.int64)
    return np.array(out, dtype=object)


def generate(seed: int, rows: dict) -> dict:
    """``rows`` maps table name to row count; returns table -> column -> array."""
    rng = np.random.default_rng(seed)
    n_cust, n_ord = rows["customer"], rows["orders"]
    custkey = np.arange(1, n_cust + 1)
    seq = np.arange(1, n_ord + 1)
    not_third = custkey[custkey % 3 != 0]
    orders = {
        "o_orderkey": ((seq >> 3) << 5) | (seq & 7),
        "o_custkey": not_third[rng.integers(0, len(not_third), n_ord)],
    }
    orders["o_comment"] = comments(rng, text_pool(rng), n_ord)
    return {"customer": {"c_custkey": custkey}, "orders": orders}
