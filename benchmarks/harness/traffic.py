"""The one general traffic generator: a mix file of statement templates
and substitution-parameter ranges becomes an endless, seeded stream of
(kind, text, parameters). A new mix is a new data file; nothing here
knows a query.

Mix file (JSON):
  loop      "closed" (each client sends its next statement when the
            previous one has answered)
  clients   how many such clients
  rotation  statement kinds in the order a client sends them, repeated
  statements.<kind>.text     SQL with {name} places
  statements.<kind>.params   {name: {"int": [lo, hi]} | {"choice": [...]}}
                             drawn uniformly from the seed
  statements.<kind>.derived  {name: rule} computed from drawn values:
      {"format": "...{year}..."}                     str.format
      {"add": [name, k]} / {"add": [name, k], "cents": true}
      {"date_minus_days": [iso, name]}               iso date - days
  statements.<kind>.parameter_sets
                             draw this many distinct parameter sets from
                             the seed and cycle through them: each is
                             warmed in set-up, and every seed sends the
                             same number of distinct programs
"""

from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    if mix["loop"] != "closed" or mix["clients"] != 1:
        raise ValueError(
            f"traffic mix {name}: only one closed-loop client is built yet "
            f"(got loop={mix['loop']!r} clients={mix['clients']!r})"
        )
    for kind in mix["rotation"]:
        if "parameter_sets" not in mix["statements"].get(kind, {}):
            raise ValueError(
                f"traffic mix {name}: statement {kind!r} is missing or has "
                "no parameter_sets"
            )
    return mix


def cents(v: int) -> str:
    return f"{v // 100}.{v % 100:02d}"


def draw(rng, spec: dict):
    if "int" in spec:
        lo, hi = spec["int"]
        return int(rng.integers(lo, hi + 1))
    if "choice" in spec:
        return spec["choice"][int(rng.integers(0, len(spec["choice"])))]
    raise ValueError(f"unknown parameter rule {spec!r}")


def derive(rule: dict, values: dict):
    if "format" in rule:
        return rule["format"].format(**values)
    if "add" in rule:
        name, k = rule["add"]
        v = values[name] + k
        return cents(v) if rule.get("cents") else v
    if "date_minus_days" in rule:
        iso, name = rule["date_minus_days"]
        return str(np.datetime64(iso, "D") - np.timedelta64(values[name], "D"))
    raise ValueError(f"unknown derived rule {rule!r}")


def instantiate(stmt: dict, rng) -> tuple:
    params = {k: draw(rng, spec) for k, spec in stmt.get("params", {}).items()}
    values = dict(params)
    for name, rule in stmt.get("derived", {}).items():
        values[name] = derive(rule, values)
    return stmt["text"].format(**values), params


def parameter_sets(mix: dict, seed: int) -> dict:
    """{kind: [(text, params), ...]}: of each kind in the rotation its
    ``parameter_sets`` distinct seeded instantiations."""
    pools = {}
    for i, (kind, stmt) in enumerate(mix["statements"].items()):
        if kind not in mix["rotation"]:
            continue
        want = int(stmt["parameter_sets"])
        rng = np.random.default_rng(np.random.SeedSequence([seed, 6, i]))
        pool: dict = {}
        for _ in range(1000 * want):
            text, params = instantiate(stmt, rng)
            pool.setdefault(text, params)
            if len(pool) == want:
                break
        else:
            raise ValueError(
                f"statement {kind!r}: its parameter ranges do not hold "
                f"{want} distinct sets"
            )
        pools[kind] = list(pool.items())
    return pools


def stream(mix: dict, seed: int):
    """Endless (kind, text, params): the rotation is fixed, so every seed
    sends the same kinds in the same order, each kind cycling through its
    seeded parameter sets."""
    pools = parameter_sets(mix, seed)
    turn = dict.fromkeys(pools, 0)
    while True:
        for kind in mix["rotation"]:
            text, params = pools[kind][turn[kind] % len(pools[kind])]
            turn[kind] += 1
            yield kind, text, params


def warm_up(mix: dict, seed: int) -> list:
    """The warm-up statements: every parameter set of every kind."""
    return [
        (kind, text, params)
        for kind, pool in parameter_sets(mix, seed).items()
        for text, params in pool
    ]
