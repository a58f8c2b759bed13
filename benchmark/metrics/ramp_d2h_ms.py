"""Pass ledger: median over the sound untraced passes and levels 1..A-1 of
``d2h_s`` — the main thread's wall inside a ramp level's ``d2h`` spans, the
harvest's fetch of the segment's output buffers, in ms.  On the mesh that was
all four shards' whole buffer sets whatever streamed; since PR 45 it is the
head of each shard's buffers unless a cursor outgrew it.  The harness reduces
it on every program that keeps the ledger (``ramp_by_seam_ms``), so it reads
before and after."""

from benchmark.harness import levelred


def read(ev):
    red = levelred.of(ev)
    by_seam = red and red["ramp_by_seam_ms"]
    return by_seam and by_seam["d2h"]
