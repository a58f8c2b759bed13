"""What one frontier-block upload costs, whole against in pieces (ISSUE 39).

The ddd level loop's upload alone, on an idle device, at the benchmark
cells' block (2^20 rows) and row widths (8, 10, 13 packed words): the whole
(block, P) buffer in one ``device_put`` — with the zero pad of the tail the
engine wrote until PR 39, and without — against the live prefix in n pieces
laid into a resident buffer by ``ddd_engine._place_piece`` (donated), for a
few piece sizes.  Each figure is the median of ``REPS`` uploads that end in
``block_until_ready``, in ms.  From them: what a piece costs (the slope over
n) and the share of the block past which one whole transfer is cheaper —
``ddd_engine._UP_WHOLE`` over ``_UP_PIECES``.

Usage: python runs/upload_pieces.py [--cpu]     (a minute on one v5e chip)
"""

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

BLOCK = 1 << 20
WORDS = (8, 10, 13)
PIECE_ROWS = (1 << 14, 1 << 15, 1 << 16)
REPS = 25


def timed(fn) -> float:
    fn()
    out = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        fn()
        out.append(time.perf_counter() - t0)
    return round(statistics.median(out) * 1e3, 3)


def main(argv) -> int:
    if "--cpu" in argv:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from raft_tla_tpu.ddd_engine import _place_piece

    dev = jax.devices()[0]
    place = jax.jit(_place_piece, donate_argnums=(0, 1))
    report = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "block": BLOCK, "reps": REPS, "words": {}}
    for P in WORDS:
        rb = np.random.default_rng(P).integers(
            0, 1 << 30, (BLOCK, P), dtype=np.int32)
        cb = np.ones((BLOCK,), bool)
        state = {"blk": jax.block_until_ready(
            (jnp.zeros((BLOCK, P), jnp.int32), jnp.zeros((BLOCK,), bool)))}

        def whole(pad_from=None):
            if pad_from is not None:
                rb[pad_from:] = 0
                cb[pad_from:] = False
            state["blk"] = None
            state["blk"] = jax.block_until_ready(
                (jax.device_put(rb), jax.device_put(cb)))

        def pieces(n, S):
            blk = state["blk"]
            for at in range(0, n * S, S):
                blk = place(*blk, rb[at:at + S], cb[at:at + S],
                            np.int32(at))
            state["blk"] = jax.block_until_ready(blk)

        row = {"whole_padded_ms": timed(lambda: whole(19)),
               "whole_ms": timed(whole), "pieces_ms": {}}
        for S in PIECE_ROWS:
            ns = [n for n in (1, 2, 4, 8, 12, 16, 24, 32)
                  if n * S <= BLOCK]
            ms = {n: timed(lambda n=n, S=S: pieces(n, S)) for n in ns}
            # a piece's cost: the slope between the first and the last
            per = (ms[ns[-1]] - ms[ns[0]]) / (ns[-1] - ns[0])
            row["pieces_ms"][S] = {
                "by_pieces": ms, "piece_ms": round(per, 4),
                "first_ms": ms[ns[0]],
                "crossing_share": round(
                    (row["whole_ms"] - ms[ns[0]]) / per * S / BLOCK
                    + S / BLOCK, 3) if per > 0 else None}
        report["words"][P] = row
        print(json.dumps({P: row}), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/upload_pieces.json", "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report["device"]))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
