"""Persons born before July 1987, counted: scan, filter, count. The date is
part of the query's text (552096000000 ms = 1987-07-01), as in a dashboard
that sends the same query again and again. Three quarters of the persons
pass it, which lies in the middle of a power-of-two bucket at SF10 (49k of
32k..64k) and at SF100 (336k of 256k..512k): no seed moves the count into
another bucket, and so into other programs."""

BORN_BEFORE = 552_096_000_000

QUERY = f"MATCH (a:Person) WHERE a.birthday < {BORN_BEFORE} RETURN count(*) AS n"


def draw_params(ref, rng):
    return {}


def reference(ref, params):
    return [{"n": int((ref.column("birthday") < BORN_BEFORE).sum())}]
