"""The one traffic generator: what a mix file's parameters make of a seed -
one text repeated, or every text of a query's parameter sets equally often in
an order the seed draws; and what it refuses."""

import decimal
import itertools
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import traffic  # noqa: E402


def take(mix, queries, seed, n):
    return list(itertools.islice(traffic.stream(mix, queries, seed), n))


@pytest.fixture(scope="module")
def queries():
    return {q: traffic.load_query(q) for q in ("q6", "q1", "q3")}


def test_fixed_text_repeats(queries):
    mix = traffic.load_mix("q6_repeat")
    sent = take(mix, queries, 2147484011, 5)
    assert len({sql for _, _, sql in sent}) == 1
    assert sent[0][0] == "q6" and "{" not in sent[0][2]
    assert traffic.warmup_texts(mix, queries) == [("q6", sent[0][2])]


def test_qgen_sets_are_the_specifications(queries):
    sets = queries["q6"]["params"]["sets"]
    assert len(sets) == 20 == len({traffic.params_key(p) for p in sets})
    assert {p["date_lo"][:4] for p in sets} == {str(y) for y in range(1993, 1998)}
    assert {p["quantity"] for p in sets} == {"24", "25"}
    mids = set()
    for p in sets:
        assert p["date_lo"][4:] == p["date_hi"][4:] == "-01-01"
        assert int(p["date_hi"][:4]) == int(p["date_lo"][:4]) + 1
        lo, hi = decimal.Decimal(p["disc_lo"]), decimal.Decimal(p["disc_hi"])
        assert hi - lo == decimal.Decimal("0.02")
        mids.add((lo + hi) / 2)
    assert mids == {decimal.Decimal(n) / 100 for n in range(2, 10)}


def test_every_seed_sends_every_text_equally_often_in_its_own_order(queries):
    mix = traffic.load_mix("q6_qgen")
    a = take(mix, queries, 3000000023, 120)
    assert a == take(mix, queries, 3000000023, 120)        # the seed decides
    b = take(mix, queries, 3000000024, 120)
    assert [sql for _, _, sql in a] != [sql for _, _, sql in b]
    for sent in (a, b):
        for k in range(6):                                 # each pass: all 20
            assert len({sql for _, _, sql in sent[20 * k:20 * k + 20]}) == 20
    assert {sql for _, _, sql in a} == {sql for _, sql in
                                        traffic.warmup_texts(mix, queries)}
    assert [sql for _, _, sql in a[:20]] != [sql for _, _, sql in a[20:40]]


def test_weights_are_drawn_from_the_seed(queries):
    mix = {"queries": [{"id": "q6", "weight": 3}, {"id": "q1", "weight": 1}],
           "params": "fixed"}
    a = take(mix, queries, 3000000023, 400)
    assert a == take(mix, queries, 3000000023, 400)
    assert a != take(mix, queries, 3000000024, 400)
    share = sum(qid == "q6" for qid, _, _ in a) / len(a)
    assert 0.65 < share < 0.85
    assert len(traffic.warmup_texts(mix, queries)) == 2


@pytest.mark.parametrize("mix,word", [
    ('{"loop": "open", "queries": []}', "open"),
    ('{"streams": 3, "queries": []}', "streams"),
    ('{"params": "qgen", "queries": []}', "params")])
def test_refuses_what_it_cannot_generate(tmp_path, monkeypatch, mix, word):
    monkeypatch.setattr(traffic, "HERE", str(tmp_path))
    os.makedirs(tmp_path / "traffic")
    (tmp_path / "traffic" / "m.json").write_text(mix)
    with pytest.raises(ValueError, match=word):
        traffic.load_mix("m")
