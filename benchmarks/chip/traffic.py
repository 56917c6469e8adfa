"""Traffic of a training cell: the token stream and the allocation schedule.

A traffic mix is a JSON file under ``traffic/`` read by the one generator
here.  Its keys:

- ``batch``, ``seq``: the global batch (rows) and the sequence length of
  every step, on every allocation;
- ``start``: the allocation (chips) the state is built on;
- ``warmup``: the allocation of each set-up step.  The first three
  set-up steps are the ones ``correct`` compares with the reference,
  so a resize mix puts an expansion and a shrink before the readings;
- ``low``, ``high``, ``period``: in the measured window the allocation
  stays ``period`` steps on ``low`` chips, then ``period`` on ``high``,
  and so on, starting on ``low``.  ``period`` 0 means no resizes.  A
  resize mix's set-up ends on ``high``, so the window's first step is a
  shrink and every cycle of ``2 * period`` steps holds one shrink and
  one expansion; the window closes only where a cycle ends;
- ``trace_steps``: how many window steps a ``--trace 1`` run records.

The tokens are a copy of ``repro.data.SyntheticTokens.sample``:
Zipf(1.3) ranks clipped to the vocabulary, deterministic per (seed,
step), so two runs with one seed train on the same rows.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
KEYS = ("batch", "seq", "start", "warmup", "low", "high", "period", "trace_steps")


@dataclass(frozen=True)
class Traffic:
    name: str
    batch: int
    seq: int
    start: int
    warmup: tuple[int, ...]
    low: int
    high: int
    period: int
    trace_steps: int

    @property
    def chips(self) -> int:
        return max(self.start, self.high, self.low, *self.warmup)

    def allocation(self, step: int) -> int:
        """Chips that step ``step`` (counted from 0 at set-up) runs on;
        ``start`` before step 0."""
        if step < 0:
            return self.start
        if step < len(self.warmup):
            return self.warmup[step]
        if self.period <= 0:
            return self.low
        k = step - len(self.warmup)
        return self.low if (k // self.period) % 2 == 0 else self.high

    def opens_cycle(self, step: int) -> bool:
        """Whether window step ``step`` starts a cycle of the schedule (every
        step does where the mix never resizes)."""
        if self.period <= 0:
            return True
        return (step - len(self.warmup)) % (2 * self.period) == 0

    def resizes(self, n_steps: int) -> list[tuple[int, int, int]]:
        """(step, chips before, chips after) for each allocation change
        before ``n_steps``: the RMS event is due at that step."""
        out = []
        prev = self.start
        for s in range(n_steps):
            now = self.allocation(s)
            if now != prev:
                out.append((s, prev, now))
            prev = now
        return out


def load(name: str) -> Traffic:
    path = HERE / "traffic" / f"{name}.json"
    raw = json.loads(path.read_text())
    missing = [k for k in KEYS if k not in raw]
    if missing:
        raise ValueError(f"traffic {name!r} lacks {missing}")
    t = Traffic(name=name, batch=int(raw["batch"]), seq=int(raw["seq"]),
                start=int(raw["start"]), warmup=tuple(int(c) for c in raw["warmup"]),
                low=int(raw["low"]), high=int(raw["high"]),
                period=int(raw["period"]), trace_steps=int(raw["trace_steps"]))
    if len(t.warmup) < 3:
        raise ValueError(f"traffic {name!r}: set-up needs three steps to compare")
    last = t.high if t.period > 0 else t.low
    if t.warmup[-1] != last:
        raise ValueError(f"traffic {name!r}: set-up ends on {last} chips, so that "
                         "the window opens a whole cycle")
    if t.period > 0 and t.trace_steps % (2 * t.period):
        raise ValueError(f"traffic {name!r}: trace_steps covers whole cycles")
    if any(t.batch % c for c in {t.low, t.high, *t.warmup}):
        raise ValueError(f"traffic {name!r}: batch {t.batch} does not split "
                         "evenly over every allocation")
    return t


class TokenStream:
    """Zipf-ish next-token stream, one batch per step, from the seed.

    ``masked_rows(step)`` may name rows whose labels are dropped (set to
    -1, which the loss masks); only the planted faults use it.
    """

    def __init__(self, vocab: int, batch: int, seq: int, seed: int,
                 masked_rows=None):
        self.vocab, self.batch, self.seq, self.seed = vocab, batch, seq, seed
        self.masked_rows = masked_rows

    def sample(self, step: int) -> dict:
        rng = np.random.default_rng((self.seed << 20) ^ step)
        ranks = rng.zipf(1.3, size=(self.batch, self.seq + 1)).astype(np.int64)
        tokens = np.minimum(ranks - 1, self.vocab - 1).astype(np.int32)
        labels = tokens[:, 1:].copy()
        if self.masked_rows is not None:
            rows = self.masked_rows(step)
            if rows is not None:
                labels[rows] = -1
        return {"labels": labels, "tokens": tokens[:, :-1]}
