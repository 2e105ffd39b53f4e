"""The comparison: what it lets pass and what it must fail."""

from fractions import Fraction

from harness import compare

REF = {
    "kinds": ["text", "int", "sum", "avg"],
    "order": [(0, "asc"), (1, "asc")],
    "rows": [("A", 1, Fraction(123456789, 100), Fraction(1, 3)),
             ("B", 2, Fraction(5, 2), Fraction(2, 7))],
}
LIMITS = compare.read_limits()


def served(rows=None):
    rows = rows or REF["rows"]
    return [(r[0], r[1], float(r[2]), float(r[3])) for r in rows]


def verdict(rows):
    return compare.judge([compare.compare_statement(rows, REF)], LIMITS)


def test_exact_answer_passes():
    v = verdict(served())
    assert v["correct"] and v["compared"]["wrong_statements"]["value"] == 0
    assert v["compared"]["sum_gap"]["value"] < 1e-15


def test_char_padding_and_float_integers_pass():
    rows = [("A ", 1.0, 1234567.89, 1 / 3), ("B", 2, 2.5, 2 / 7)]
    assert verdict(rows)["correct"]


def test_an_altered_answer_fails():
    rows = served()
    rows[1] = (rows[1][0], rows[1][1], rows[1][2] * (1 + 1e-9), rows[1][3])
    v = verdict(rows)
    assert not v["correct"]
    assert v["compared"]["sum_gap"]["value"] > LIMITS["sum_gap"]


def test_a_wrong_key_a_missing_row_and_a_wrong_order_fail():
    rows = served()
    assert not verdict([("A", 1, 1234567.89, 1 / 3), ("C", 2, 2.5, 2 / 7)])["correct"]
    assert not verdict(rows[:1])["correct"]
    assert not verdict(rows[::-1])["correct"]
    assert not verdict(None)["correct"]


def test_float32_average_passes_bfloat16_fails():
    import numpy as np

    rows = served()
    f32 = float(np.float32(1 / 3))
    assert verdict([(rows[0][0], 1, rows[0][2], f32), rows[1]])["correct"]
    bf16 = 0.333984375  # 1/3 in bfloat16
    v = verdict([(rows[0][0], 1, rows[0][2], bf16), rows[1]])
    assert not v["correct"]
    assert v["compared"]["avg_gap"]["value"] > LIMITS["avg_gap"]


def test_nothing_compared_is_not_correct():
    assert not compare.judge([], LIMITS)["correct"]
