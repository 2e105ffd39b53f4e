"""The traffic generator: a mix's seeded parameter sets, the stream that
cycles through them and the warm-up list that covers them."""

import itertools
import json

import pytest

from harness import traffic


def test_stream_cycles_the_sets_the_warm_up_covers():
    mix = traffic.read_mix("scan_q1_q6")
    seed = 2**31 + 99
    sent = list(itertools.islice(traffic.stream(mix, seed), 36))
    assert [k for k, _t, _p in sent[:6]] == ["q6", "q6", "q1"] * 2
    texts = {k: [t for kk, t, _p in sent if kk == k] for k in ("q6", "q1")}
    assert len(set(texts["q6"])) == 6 and len(set(texts["q1"])) == 3
    assert texts["q6"][:6] == texts["q6"][6:12]  # in the same order again
    warm = traffic.warm_up(mix, seed)
    assert {t for _k, t, _p in warm} == {t for _k, t, _p in sent}
    assert len(warm) == 9
    for _k, text, p in sent:
        assert "{" not in text
        if "discount" in p:
            assert 1993 <= p["year"] <= 1997 and 2 <= p["discount"] <= 9
            assert f"date '{p['year']}-01-01'" in text
            assert f"date '{p['year'] + 1}-01-01'" in text
            assert f"between 0.0{p['discount']} - 0.01" in text


def test_same_seed_same_stream_other_seed_other_sets():
    mix = traffic.read_mix("join_q3")
    a, b, c = (
        [t for _k, t, _p in itertools.islice(traffic.stream(mix, s), 8)]
        for s in (7, 7, 8)
    )
    assert a == b and set(a) != set(c)
    assert len(set(a)) == 4 and a[:4] == a[4:]


def test_a_statement_without_parameter_sets_is_refused(tmp_path, monkeypatch):
    (tmp_path / "traffic").mkdir()
    mix = {"loop": "closed", "clients": 1, "rotation": ["q"],
           "statements": {"q": {"text": "select 1"}}}
    (tmp_path / "traffic" / "m.json").write_text(json.dumps(mix))
    monkeypatch.setattr(traffic, "HERE", str(tmp_path))
    with pytest.raises(ValueError, match="parameter_sets"):
        traffic.read_mix("m")
