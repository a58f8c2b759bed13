"""A deep frontier block in plain Python, shared by the segment-level
tests of the one-chip and the mesh engine (``tests/test_ddd_engine.py``,
``tests/test_ddd_shard_engine.py``)."""

import numpy as np

from raft_tla_tpu.models import interp


def frontier_block(cfg, depth, n_rows):
    """The first ``n_rows`` states of BFS level ``depth`` (plain Python,
    models/interp) as unpacked rows and their constraint flags."""
    seen = {interp.init_state(cfg.bounds)}
    level = list(seen)
    for _ in range(depth):
        nxt = []
        for s in level:
            if not interp.constraint_ok(s, cfg.bounds):
                continue             # kept, never expanded (refbfs)
            for _a, t in interp.successors(s, cfg.bounds, spec=cfg.spec):
                if t not in seen:
                    seen.add(t)
                    nxt.append(t)
        level = nxt
    level = level[:n_rows]
    assert len(level) == n_rows
    vecs = np.stack([interp.to_vec(s, cfg.bounds) for s in level])
    con = np.array([interp.constraint_ok(s, cfg.bounds) for s in level])
    return vecs, con
