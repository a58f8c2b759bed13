"""Operations and bytes of the fused step, from shapes alone.

``scan_words`` is the analytic word count of the orbit scan copied from
bench.py:210-214 (``chunk * A * |G| * width``): every candidate lane of a
chunk is permuted, canonicalised and fingerprinted once per group element.
"""

from __future__ import annotations

import math

BUCKET = 8      # filter-table bucket width (device_engine.BUCKET)


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def chunk_steps(pins: list, a: int, b: int, block: int, chunk: int,
                ndev: int = 1) -> int:
    """Chunk steps a pass runs between the stamps at levels ``a`` and
    ``b``: it expands the frontiers of levels a..b-1 block by block,
    ``chunk`` rows to a step.  On a mesh of ``ndev`` shards a window is
    ``ndev`` blocks, dealt whole block by whole block (shard 0 takes the
    first ``block`` rows, shard 1 the next: ``DDDShardEngine._upload_window``),
    and the shards step in lockstep: a window runs as many steps as its
    fullest shard, which is shard 0."""
    steps = 0
    for lvl in range(a, b):
        rows = pins[lvl] - (pins[lvl - 1] if lvl else 0)
        for start in range(0, rows, ndev * block):
            steps += _ceil_div(min(block, rows - start), chunk)
    return steps


def scan_words(chunk: int, n_actions: int, n_servers: int, width: int,
               symmetry: bool) -> int:
    """32-bit words the orbit scan touches in one chunk step."""
    group = math.factorial(n_servers) if symmetry else 1
    return chunk * n_actions * group * width


def step_bytes(chunk: int, n_actions: int, packed_words: int,
               ndev: int = 1, send: int | None = None) -> int:
    """HBM bytes one chunk step must move whatever the schedule: the
    chunk's packed frontier rows in, and one filter bucket (hi and lo
    words) gathered for every candidate lane.  On a mesh this is ONE
    shard's lockstep step: its chunk in, and a bucket for every lane it
    receives from the exchange, ``send`` from each of the ``ndev`` shards
    (``chunk * n_actions`` unless the capacities cut it), live or not.  What
    the exchange itself moves over the links is not HBM traffic and is left
    out."""
    send = chunk * n_actions if send is None else send
    return chunk * packed_words * 4 + ndev * send * BUCKET * 8


def export_bytes(rows: int, packed_words: int) -> int:
    """Bytes of the candidate stream written for ``rows`` exported rows:
    packed row, two key words, parent, lane, constraint flag."""
    return rows * (packed_words + 5) * 4
