"""The closed loop ends a window on a whole rotation of its mix."""

import itertools
import time
from types import SimpleNamespace

from harness import client_loop


class FakeClient:
    def __init__(self, fail_on=()):
        self.sent = []
        self.fail_on = set(fail_on)

    def execute(self, text):
        self.sent.append(text)
        time.sleep(0.002)
        if len(self.sent) in self.fail_on:
            raise RuntimeError("refused")
        return SimpleNamespace(rows=[(len(self.sent),)])


def stream():
    for i in itertools.count():
        kind = ("a", "a", "b")[i % 3]
        yield kind, f"{kind}{i}", {"i": i}


def test_a_window_is_whole_rotations_and_counts_everything():
    client = FakeClient(fail_on={2})
    res = client_loop.closed_loop(client, stream(), 0.02, 3)
    done = res["statements"]
    assert len(done) % 3 == 0 and len(done) >= 9
    assert [s["kind"] for s in done[:3]] == ["a", "a", "b"]
    assert res["window_s"] >= 0.02
    # the window is the time of all its statements, the failed one too
    assert done[1]["error"] == "RuntimeError: refused" and done[1]["rows"] is None
    assert done[0]["rows"] == [(1,)] and done[0]["error"] is None
    assert sum(s["ms"] for s in done) / 1000.0 <= res["window_s"]
    # one rotation short of the length would have ended before it
    assert sum(s["ms"] for s in done[:-3]) / 1000.0 < 0.02 + 0.01


def test_a_span_wraps_each_statement():
    seen = []

    class Span:
        def __init__(self, kind):
            seen.append(kind)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    res = client_loop.closed_loop(FakeClient(), stream(), 0.0, 3, Span)
    assert seen == ["a", "a", "b"] and len(res["statements"]) == 3
