"""The closed loop: one client sends a statement, waits for its last row
and sends the next. The window ends at the first completion of a whole
rotation of the mix at or after its length: a count of long statements is
not cut at a fixed edge, and every window holds the mix's kinds in the
mix's own proportions (a window cut inside a rotation of a short and a
long kind reads a rate that swings with where the cut falls)."""

from __future__ import annotations

import time


def closed_loop(client, statements, seconds: float, rotation: int,
                span=None) -> dict:
    """Drive ``statements`` (an iterator of (kind, text, params), whole
    rotations of ``rotation`` statements) for ``seconds``. Returns every
    statement with its latency, its rows or its error. ``span(kind)``
    gives an optional context manager around each statement (the traced
    run's annotations)."""
    done = []
    t0 = time.perf_counter()
    end = t0
    for kind, text, params in statements:
        ts = time.perf_counter()
        rows = err = None
        try:
            if span is None:
                rows = client.execute(text).rows
            else:
                with span(kind):
                    rows = client.execute(text).rows
        except Exception as e:  # a failed statement counts in `failed`
            err = f"{type(e).__name__}: {e}"
        end = time.perf_counter()
        done.append({
            "kind": kind, "text": text, "params": params,
            "ms": (end - ts) * 1000.0, "rows": rows, "error": err,
        })
        if end - t0 >= seconds and len(done) % rotation == 0:
            break
    return {"window_s": end - t0, "statements": done}
