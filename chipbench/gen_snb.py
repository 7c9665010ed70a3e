"""The benchmark's data generator: LDBC SNB's Person and KNOWS from a seed.

NumPy only: imports nothing of the program, so no later change to the
program changes the data a cell runs on. There is no network and no datagen
here, so this is a stand-in for LDBC's datagen that keeps what the
specification publishes and a configuration states:

* the number of persons and of KNOWS pairs of the scale factor, exactly
  (``persons``, ``knows`` in the configuration's file);
* the whole Person row: ``id``, ``firstName``, ``lastName``, ``gender``,
  ``birthday``, ``creationDate``, ``locationIP``, ``browserUsed``; dates as
  64-bit milliseconds since 1970, as the Cypher implementation of
  Interactive v1 loads them. A KNOWS row has its ``creationDate``;
* 64-bit person ids laid out as datagen's: the person's serial number in the
  low 41 bits, the seventeenth of the simulated three years (2010 to 2012)
  in which the person joined above them (933, 2199023255594, ...,
  35184372088856 at SF1);
* a degree distribution of Facebook's shape (Ugander et al. 2011: the mean
  about twice the median, no power law), scaled to the scale factor's own
  mean degree 2 * knows / persons and capped at 1,000 friends, and friends
  found along three orderings of the persons (45% by where they are, 45% by
  what they like, 10% at random), which is what gives the graph its
  triangles.

What is assumed here and not LDBC's is listed in the configuration under
``assumed``: the curve itself (a lognormal with sigma 1.14 in place of
datagen's table of percentiles), the window of the matching, the
dictionaries of names, and the order of the rows.

KNOWS is undirected in LDBC and stored once; ``src``/``dst`` hold every pair
in both directions (``2 * knows`` rows), as a loader for an engine that
expands along a direction stores it (``tpu_cypher.io.ldbc.load_snb_csv``
does). Deterministic per seed; any seed NumPy's ``default_rng`` takes.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

DAY_MS = 86_400_000
JOINED_FROM = 14_610 * DAY_MS  # 2010-01-01
JOINED_TO = 15_706 * DAY_MS  # 2013-01-01
BORN_FROM = 3_652  # 1980-01-01, days since 1970
BORN_TO = 7_305  # 1990-01-01
ID_BUCKETS = 17
ID_SERIAL_BITS = 41
MAX_FRIENDS = 1_000
DEGREE_SIGMA = 1.14  # lognormal: mean / median = exp(sigma**2 / 2) = 1.92
SHARES = (0.45, 0.45, 0.10)  # by place, by interest, at random
JITTER = 1000.0  # positions: how far along an ordering a friend is looked for
SPARE = 1.12  # pairs matched beyond ``knows``, before doubles are dropped
BROWSERS = ("Firefox", "Chrome", "Internet Explorer", "Safari", "Opera")
BROWSER_SHARES = (0.31, 0.29, 0.27, 0.08, 0.05)
_ONSETS = "b d f g h j k l m n p r s t v z ch sh th br".split()
_VOWELS = "a e i o u ai ei ou".split()


def _names(count: int, syllables: int) -> np.ndarray:
    """A fixed dictionary of ``count`` distinct pronounceable names."""
    every = [o + v for o in _ONSETS for v in _VOWELS]
    total = len(every) ** syllables
    out = []
    for k in range(count):
        x, word = (k * 7919) % total, ""  # a prime stride: out of order
        for _ in range(syllables):
            word += every[x % len(every)]
            x //= len(every)
        out.append(word.capitalize())
    return np.array(out)


FIRST_NAMES = _names(2_000, 2)
LAST_NAMES = _names(5_000, 3)


def _match(rng, order_key: np.ndarray, stubs: np.ndarray, jitter: float):
    """Pairs of stubs that lie near each other along one ordering."""
    pos = np.empty(len(order_key), dtype=np.float64)
    pos[np.argsort(order_key, kind="stable")] = np.arange(len(order_key))
    where = pos[stubs] + rng.normal(0.0, jitter, size=len(stubs))
    stubs = stubs[np.argsort(where)]
    half = len(stubs) // 2
    return stubs[0:2 * half:2], stubs[1:2 * half:2]


def snb_arrays(persons: int, knows: int, seed: int) -> Dict[str, np.ndarray]:
    n = int(persons)
    knows = min(int(knows), n * (n - 1) // 4)
    rng = np.random.default_rng(seed)

    joined = rng.integers(JOINED_FROM, JOINED_TO, size=n, dtype=np.int64)
    bucket = (joined - JOINED_FROM) * ID_BUCKETS // (JOINED_TO - JOINED_FROM)
    ids = (bucket << ID_SERIAL_BITS) | np.arange(n, dtype=np.int64)
    birthday = rng.integers(BORN_FROM, BORN_TO, size=n, dtype=np.int64) * DAY_MS
    first = (len(FIRST_NAMES) * rng.random(n) ** 2.5).astype(np.int64)
    last = (len(LAST_NAMES) * rng.random(n) ** 1.5).astype(np.int64)
    octets = rng.integers(1, 255, size=(4, n))
    ip = octets[0].astype(str)
    for k in (1, 2, 3):
        ip = np.char.add(np.char.add(ip, "."), octets[k].astype(str))
    browser = rng.choice(len(BROWSERS), size=n, p=BROWSER_SHARES)

    # how many friends each person is to have, then friends by stub matching
    cap = min(MAX_FRIENDS, max(n // 4, 1))
    want = rng.lognormal(0.0, DEGREE_SIGMA, size=n)
    for _ in range(8):  # scale to the mean degree under the cap
        want = np.minimum(want * (2.0 * knows / n) / want.mean(), cap)
    orderings = (
        octets[0] * 4_000.0 + birthday / DAY_MS + rng.random(n),  # place, age
        first + rng.random(n),  # interest
        rng.random(n),
    )
    pairs = np.empty(0, dtype=np.int64)
    spare = SPARE
    for _ in range(64):
        if len(pairs) >= knows:
            break
        found = [pairs]
        for share, key in zip(SHARES, orderings):
            quota = np.floor(want * share * spare + rng.random(n)).astype(np.int64)
            a, b = _match(rng, key, np.repeat(np.arange(n), quota),
                          min(JITTER, n / 8.0))
            lo, hi = np.minimum(a, b), np.maximum(a, b)
            found.append((lo * n + hi)[lo != hi])
        pairs = np.unique(np.concatenate(found))
        spare = 0.1  # a top-up round, if doubles took more than the spare
    knows = min(knows, len(pairs))  # a tiny rehearsal graph may be full
    drop = rng.choice(len(pairs), size=len(pairs) - knows, replace=False)
    pairs = np.delete(pairs, drop)  # stays in the order of the first person
    a, b = pairs // n, pairs % n
    since = np.maximum(joined[a], joined[b])
    since = since + (rng.random(knows) ** 2 * (JOINED_TO - since)).astype(np.int64)

    return {
        "ids": ids,
        "firstName": FIRST_NAMES[first],
        "lastName": LAST_NAMES[last],
        "gender": np.array(["female", "male"])[rng.integers(0, 2, size=n)],
        "birthday": birthday,
        "creationDate": joined,
        "locationIP": ip,
        "browserUsed": np.array(BROWSERS)[browser],
        "src": ids[np.concatenate([a, b])],
        "dst": ids[np.concatenate([b, a])],
        "knows_creationDate": np.concatenate([since, since]),
    }
