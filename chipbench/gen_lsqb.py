"""LSQB's Person side from a seed: ``gen_snb``'s persons and friendships,
and the four tables the LSQB queries that read no Message walk beside them.

LSQB (the LDBC Labelled Subgraph Query Benchmark, github.com/ldbc/lsqb)
runs over the SNB datagen's data set projected to ids: its files hold, for
every node and edge table, the ids and nothing else. This stand-in keeps
that: ``ids`` / ``src`` / ``dst`` are ``gen_snb.snb_arrays``' own for the
seed (the same persons and friendships as the ``snb-sf*`` configurations,
KNOWS stored in both directions), and on top of them

* ``city_ids``, ``country_ids``, ``tag_ids`` — the node tables City, Country
  and Tag (counts in the configuration's ``assumed``);
* ``person_city`` — isLocatedIn, one city id per person, in the order of
  ``ids``: a person's city is drawn along the very ordering ``gen_snb``
  matches 45% of the friendships along (where the person is, then the
  age), so friends share cities and countries far more often than chance,
  as datagen's do;
* ``city_country`` — isPartOf, one country id per city, in the order of
  ``city_ids``: neighbouring cities of that ordering share a country;
* ``interest_person`` / ``interest_tag`` — hasInterest, about 23.2 tags a
  person, no pair twice: a Zipf-like popularity over the tags, shifted by
  the person's country so that countries differ in what is popular.

LSQB's files give every label its own id space; the engine has one, so a
city, country or tag id carries its label in bits 46-47 (person ids stay
datagen's, below ``17 << 41``). NumPy only; deterministic per seed.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

import gen_snb

# scale factor 10's counts beside its 65,645 persons; another number of
# persons (a rehearsal) takes the same shares
SF10_PERSONS = 65_645
CITIES, COUNTRIES, TAGS = 1_343, 111, 16_080
INTERESTS_A_PERSON = 23.2
LABEL_SHIFT = 46
CITY, COUNTRY, TAG = 1, 2, 3
SIZE_SKEW = 1.6  # cities and countries: sizes along a power curve
TAG_SKEW = 2.5  # a tag's rank is tags * u ** TAG_SKEW: a heavy head
SPARE_DRAWS = 1.009  # drawn beyond the mean, before double pairs are dropped


def _scaled(count: int, persons: int, least: int) -> int:
    return max(least, round(count * persons / SF10_PERSONS))


def _along(position: np.ndarray, groups: int) -> np.ndarray:
    """The group (0 .. groups-1) of each position in [0, 1): consecutive
    stretches of unequal length, the first the longest."""
    edges = np.linspace(0.0, 1.0, groups + 1) ** (1.0 / SIZE_SKEW)
    return np.minimum(np.searchsorted(edges, position, side="right") - 1,
                      groups - 1)


def snb_arrays(persons: int, knows: int, seed: int) -> Dict[str, np.ndarray]:
    base = gen_snb.snb_arrays(persons, knows, seed)
    ids = base["ids"]
    n = len(ids)
    rng = np.random.default_rng([seed, 32])
    cities = _scaled(CITIES, n, 3)
    countries = min(_scaled(COUNTRIES, n, 2), cities)
    tags = _scaled(TAGS, n, 16)

    # gen_snb's first ordering: the IP's first octet, then the birthday
    octet = np.array([ip.split(".", 1)[0] for ip in base["locationIP"]],
                     dtype=np.int64)
    place = octet * 4_000.0 + base["birthday"] / gen_snb.DAY_MS
    rank = np.empty(n, dtype=np.float64)
    rank[np.argsort(place, kind="stable")] = np.arange(n)
    city_of = _along(rank / n, cities)
    country_of_city = _along((np.arange(cities) + 0.5) / cities, countries)

    want = rng.poisson(INTERESTS_A_PERSON * SPARE_DRAWS, size=n)
    who = np.repeat(np.arange(n), want)
    popular = (tags * rng.random(len(who)) ** TAG_SKEW).astype(np.int64)
    shift = country_of_city[city_of[who]] * max(tags // countries, 1)
    pair = np.unique(who * tags + (popular + shift) % tags)

    def labelled(label: int, count: int) -> np.ndarray:
        return (np.int64(label) << LABEL_SHIFT) | np.arange(count, dtype=np.int64)

    city_ids, country_ids = labelled(CITY, cities), labelled(COUNTRY, countries)
    tag_ids = labelled(TAG, tags)
    return {
        "ids": ids,
        "src": base["src"],
        "dst": base["dst"],
        "city_ids": city_ids,
        "country_ids": country_ids,
        "tag_ids": tag_ids,
        "person_city": city_ids[city_of],
        "city_country": country_ids[country_of_city],
        "interest_person": ids[pair // tags],
        "interest_tag": tag_ids[pair % tags],
    }
