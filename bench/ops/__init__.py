"""Op drivers: one module per operation a traffic mix can name.

Each module provides ``prepare(config, traffic, seed) -> state``,
``operands(state, i)`` (the i-th operation's inputs, from a ring of value
arrays made in ``prepare``), ``call(state, operands) -> (output,
counters)`` (the timed call into the program), ``keep(output)`` (what the
comparison needs of it), ``check(state, i, kept) -> numbers``, ``control(
state, i) -> kept`` (the reference one precision step down, in the
program's place) and ``work(state)`` (operations and bytes of one call,
counted from the pattern).
"""
from __future__ import annotations

import gc

from repro.runtime import ReapRuntime


class RecordingRuntime(ReapRuntime):
    """The program's runtime, keeping the stats of each ``run`` call so a
    driver can read the program's own spans and counters."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.log = []

    def run(self, op_tag, *operands, **kw):
        result, stats = super().run(op_tag, *operands, **kw)
        self.log.append((op_tag, stats))
        return result, stats


class State:
    """What a driver keeps between calls: the pattern, the value ring, the
    runtime, and references computed after the window."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.cache = {}

    def release(self) -> None:
        """Drop the runtime and its plans before the references run."""
        self.rt = None
        gc.collect()
