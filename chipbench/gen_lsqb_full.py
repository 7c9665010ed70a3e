"""LSQB's whole data set from a seed: ``gen_snb``'s persons and friendships
for the seed, to the row; the Person side by ``gen_lsqb``'s rules and from
its random stream (City, Country, Tag, isLocatedIn, isPartOf, hasInterest),
and the Message side the other six queries walk — Forum, Post, Comment
(both also Message), TagClass; hasMember, containerOf, hasCreator, likes,
replyOf, hasTag of a message, hasType. As LSQB's projected files do, a node
is its id and an edge its two ends. The static tables (City, Country, Tag,
TagClass) stand at the specification's counts, which are the same at every
scale factor (``gen_lsqb`` makes them a share of scale factor 10's persons:
a third of them here); every count is a share of scale factor 3's persons
(``table_counts``), so a rehearsal scales. The shapes datagen gives and this
stand-in keeps:

* a forum has a moderator, drawn in proportion to the friends a person
  has, and its members are friends of the moderator (a heavy-tailed number
  of them, at most all); a popular forum holds more posts;
* a message's creator is a member of its forum (the moderator where the
  forum has none), a comment's forum that of the post its thread hangs on;
* a comment replies to a post or to an earlier comment, half each, and a
  thread's size is heavy-tailed: the parent is drawn at ``n * u ** 2`` of
  the ``n`` candidates, so few messages collect most replies;
* likes fall on messages by the same curve over the same order, so the
  messages with many replies are the messages with many likes (LSQB Q4's
  count is their product); no (person, message) pair twice;
* a post's tags sit round a main tag drawn by popularity and shifted by
  its forum's topic; a comment takes its parent's main tag 7 times in 10;
  over half the comments carry no tag, no (message, tag) pair twice;
* a tag has one class, classes of unequal size along the tags' popularity.

The engine has one id space: City, Country and Tag ids are ``gen_lsqb``'s
(the label in bits 46-47), person ids datagen's (under ``17 << 41``), and
the four new labels take free codes of the four bits from 44 up. NumPy
only; deterministic per seed.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

import gen_lsqb
import gen_snb

# scale factor 3's counts beside its 24,328 persons: scale factor 1's
# (90,492 forums, 1,003,605 posts, 2,052,169 comments, 1,611,869 hasMember,
# 2,190,095 likes, 3,411,651 hasTag of a message) times the ratio of the
# node totals, 9,281,922 / 3,181,724; and the static tables, the same at
# every scale factor: 1,343 cities, 111 countries, 16,080 tags, 71 classes
SF3_PERSONS = 24_328
SF3 = {
    "forums": 263_993, "posts": 2_927_838, "comments": 5_986_826,
    "members": 4_702_282, "likes": 6_389_190, "message_tags": 9_952_840,
    "cities": gen_lsqb.CITIES, "countries": gen_lsqb.COUNTRIES,
    "tags": gen_lsqb.TAGS, "tag_classes": 71,
}
LEAST = {"forums": 8, "posts": 24, "comments": 48, "members": 32,
         "likes": 48, "message_tags": 64, "cities": 3, "countries": 2,
         "tags": 16, "tag_classes": 3}
LABEL_SHIFT = 44  # codes 4, 8 and 12 are gen_lsqb's City, Country and Tag
TAGCLASS, FORUM, POST, COMMENT = 5, 6, 9, 10
POST_TAGS = 1.9  # mean tags a post; the comments take what is left
INHERIT = 0.7  # a comment's main tag is its parent's
SPARE_DRAWS = 1.03  # likes drawn beyond the count, before double pairs go
FORUM_SIGMA = 1.0  # the lognormal a forum's size is drawn from
CLASS_SKEW = 1.6
TAG_SKEW = 2.5  # gen_lsqb's: a tag's rank is tags * u ** TAG_SKEW


def table_counts(persons: int) -> Dict[str, int]:
    """The tables' row counts for this many persons: the share of scale
    factor 3's, which at its 24,328 persons is the specification's own.
    What the generator aims at (hasMember, likes and hasTag lose their
    double draws: within 3%), and what the roofline of the tree count
    reckons with."""
    got = {name: max(LEAST[name], round(count * persons / SF3_PERSONS))
           for name, count in SF3.items()}
    got["countries"] = min(got["countries"], got["cities"])
    got["messages"] = got["posts"] + got["comments"]
    return got


def _labelled(code: int, count: int) -> np.ndarray:
    return (np.int64(code) << LABEL_SHIFT) | np.arange(count, dtype=np.int64)


def _resolve(value: np.ndarray, up: np.ndarray) -> np.ndarray:
    """``value`` where it is known (>= 0), else the value of the nearest
    known ancestor along ``up`` (an earlier row each): pointer jumping, a
    few rounds of whole-array gathers."""
    value, up = value.copy(), up.copy()
    while True:
        todo = np.flatnonzero(value < 0)
        if not len(todo):
            return value
        above = up[todo]
        known = value[above] >= 0
        value[todo[known]] = value[above[known]]
        up[todo[~known]] = up[above[~known]]


def _person_side(base: Dict[str, np.ndarray], seed: int,
                 want: Dict[str, int]) -> Dict[str, np.ndarray]:
    """City, Country, Tag, isLocatedIn, isPartOf and hasInterest as
    ``gen_lsqb.snb_arrays`` draws them (its rules, constants and random
    stream: a person's city along the ordering ``gen_snb`` matches friends
    along, unequal cities and countries, interests by a popularity shifted
    by the country), over ``want``'s numbers of cities, countries and tags."""
    ids = base["ids"]
    n = len(ids)
    rng = np.random.default_rng([seed, 32])
    cities, countries, tags = want["cities"], want["countries"], want["tags"]
    octet = np.array([ip.split(".", 1)[0] for ip in base["locationIP"]],
                     dtype=np.int64)
    place = octet * 4_000.0 + base["birthday"] / gen_snb.DAY_MS
    rank = np.empty(n, dtype=np.float64)
    rank[np.argsort(place, kind="stable")] = np.arange(n)
    city_of = gen_lsqb._along(rank / n, cities)
    country_of_city = gen_lsqb._along((np.arange(cities) + 0.5) / cities, countries)
    drawn = rng.poisson(gen_lsqb.INTERESTS_A_PERSON * gen_lsqb.SPARE_DRAWS, size=n)
    who = np.repeat(np.arange(n), drawn)
    popular = (tags * rng.random(len(who)) ** TAG_SKEW).astype(np.int64)
    shift = country_of_city[city_of[who]] * max(tags // countries, 1)
    pair = np.unique(who * tags + (popular + shift) % tags)

    def labelled(label: int, count: int) -> np.ndarray:
        return ((np.int64(label) << gen_lsqb.LABEL_SHIFT)
                | np.arange(count, dtype=np.int64))

    city_ids = labelled(gen_lsqb.CITY, cities)
    country_ids = labelled(gen_lsqb.COUNTRY, countries)
    tag_ids = labelled(gen_lsqb.TAG, tags)
    return {
        "ids": ids, "src": base["src"], "dst": base["dst"],
        "city_ids": city_ids, "country_ids": country_ids, "tag_ids": tag_ids,
        "person_city": city_ids[city_of],
        "city_country": country_ids[country_of_city],
        "interest_person": ids[pair // tags],
        "interest_tag": tag_ids[pair % tags],
    }


def snb_arrays(persons: int, knows: int, seed: int) -> Dict[str, np.ndarray]:
    persons_base = gen_snb.snb_arrays(persons, knows, seed)
    n = len(persons_base["ids"])
    want = table_counts(n)
    base = _person_side(persons_base, seed, want)
    ids = base["ids"]
    rng = np.random.default_rng([seed, 34])
    forums, posts, comments = want["forums"], want["posts"], want["comments"]
    tags = want["tags"]

    # every person's friends, a run of KNOWS rows each
    order = np.argsort(ids)
    by_id = ids[order]
    s = order[np.searchsorted(by_id, base["src"])]
    d = order[np.searchsorted(by_id, base["dst"])]
    by_source = np.argsort(s, kind="stable")
    friend = d[by_source]
    start = np.searchsorted(s[by_source], np.arange(n + 1))
    deg = np.diff(start)

    # forums: the moderator, then some of the moderator's friends
    moderator = (s[rng.integers(0, len(s), forums)] if len(s)
                 else rng.integers(0, n, forums))
    mean = 1.15 * want["members"] / forums  # the cap at all friends takes some
    drawn = rng.lognormal(np.log(mean) - FORUM_SIGMA ** 2 / 2, FORUM_SIGMA, forums)
    size = np.minimum(deg[moderator], drawn.astype(np.int64))
    forum_of = np.repeat(np.arange(forums), size)
    first_member = np.cumsum(size) - size
    nth = np.arange(len(forum_of)) - first_member[forum_of]
    turn = (rng.random(forums) * deg[moderator]).astype(np.int64)
    run = np.maximum(deg[moderator], 1)[forum_of]
    member = friend[start[moderator][forum_of] + (turn[forum_of] + nth) % run]

    def someone_of(forum: np.ndarray) -> np.ndarray:
        """A member of each forum, the early ones of its list more often;
        the moderator of a forum without members."""
        at = (size[forum] * rng.random(len(forum)) ** 2).astype(np.int64)
        held = np.minimum(first_member[forum] + at, max(len(member) - 1, 0))
        return np.where(size[forum] > 0,
                        member[held] if len(member) else moderator[forum],
                        moderator[forum])

    # posts: a forum in proportion to its size, a member as the creator
    weight = np.cumsum(size + 1.0)
    post_forum = np.minimum(
        np.searchsorted(weight, rng.random(posts) * weight[-1], side="right"),
        forums - 1)
    post_creator = someone_of(post_forum)

    # comments: a reply to a post or to an earlier comment, few of either
    # collecting most; the thread's post gives the forum
    j = np.arange(comments)
    to_post = (rng.random(comments) < 0.5) | (j == 0)
    u = rng.random(comments) ** 2
    parent_post = np.where(to_post, (posts * u).astype(np.int64), -1)
    parent_comment = np.where(to_post, 0, (j * u).astype(np.int64))
    thread = _resolve(parent_post, parent_comment)
    comment_forum = post_forum[thread]
    comment_creator = someone_of(comment_forum)

    # likes: the same curve over the same order of posts and of comments
    draws = int(want["likes"] * SPARE_DRAWS)
    on_post = rng.random(draws) < posts / (posts + comments)
    u = rng.random(draws) ** 2
    liked = np.where(on_post, (posts * u).astype(np.int64),
                     posts + (comments * u).astype(np.int64))
    active = rng.permutation(n)
    liker = active[(n * rng.random(draws) ** 1.5).astype(np.int64)]
    pair = np.unique(liker * np.int64(posts + comments) + liked)
    if len(pair) > want["likes"]:
        pair = pair[np.sort(rng.choice(len(pair), want["likes"], replace=False))]

    # tags: a main tag a message, a comment's mostly its parent's
    topic = rng.integers(0, tags, forums)

    def fresh(forum: np.ndarray) -> np.ndarray:
        popular = (tags * rng.random(len(forum)) ** TAG_SKEW).astype(np.int64)
        return (popular + topic[forum]) % tags

    post_main = fresh(post_forum)
    inherits = rng.random(comments) < INHERIT
    comment_main = np.where(
        inherits, np.where(to_post, post_main[np.maximum(parent_post, 0)], -1),
        fresh(comment_forum))
    comment_main = _resolve(comment_main, parent_comment)
    left = (want["message_tags"] - POST_TAGS * posts) / comments
    tagged = rng.random(comments) < 0.45
    count = np.concatenate([
        1 + rng.poisson(POST_TAGS - 1.0, posts),
        tagged * (1 + rng.poisson(max(left / 0.45 - 1.0, 0.0), comments)),
    ])
    main = np.concatenate([post_main, comment_main])
    holder = np.repeat(np.arange(posts + comments), count)
    nth = np.arange(len(holder)) - np.repeat(np.cumsum(count) - count, count)
    beside = nth * (1 + (7 * rng.random(len(holder)) ** 2).astype(np.int64))
    tagging = np.unique(holder * np.int64(tags) + (main[holder] + beside) % tags)

    classes = min(want["tag_classes"], tags)
    tag_class = gen_lsqb._along((np.arange(tags) + 0.5) / tags, classes)

    forum_ids, post_ids = _labelled(FORUM, forums), _labelled(POST, posts)
    comment_ids = _labelled(COMMENT, comments)
    tagclass_ids = _labelled(TAGCLASS, classes)
    message_ids = np.concatenate([post_ids, comment_ids])
    return {
        **base,
        "tagclass_ids": tagclass_ids,
        "forum_ids": forum_ids,
        "post_ids": post_ids,
        "comment_ids": comment_ids,
        "tag_class": tagclass_ids[tag_class],
        "member_forum": forum_ids[forum_of],
        "member_person": ids[member] if len(member) else ids[:0],
        "post_forum": forum_ids[post_forum],
        "post_creator": ids[post_creator],
        "comment_creator": ids[comment_creator],
        "comment_parent": np.where(
            to_post, post_ids[np.maximum(parent_post, 0)], comment_ids[parent_comment]),
        "like_person": ids[pair // (posts + comments)],
        "like_message": message_ids[pair % (posts + comments)],
        "msgtag_message": message_ids[tagging // tags],
        "msgtag_tag": base["tag_ids"][tagging % tags],
    }
