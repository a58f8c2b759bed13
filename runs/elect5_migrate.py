"""Standalone full->frontier migration of the elect5 campaign
checkpoint (round 5): the migration is pure host-side file slicing
(load_frontier_snapshot), so it can run while the chip is unreachable —
a returning chip then resumes straight into the first dispatch instead
of spending its window on a 63 GB rewrite.  Idempotent: if the
checkpoint is already frontier-format this is a no-op open+verify."""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

from raft_tla_tpu.config import Bounds, CheckConfig
from raft_tla_tpu.ddd_engine import _DigestCaps, load_frontier_snapshot
from raft_tla_tpu.models import interp
from raft_tla_tpu.ops import bitpack, symmetry as sym_mod
from raft_tla_tpu.utils import ckpt

RUNS = os.path.dirname(os.path.abspath(__file__))
CKPT = os.path.join(RUNS, "elect5ddd.ckpt")

CFG = CheckConfig(
    bounds=Bounds(n_servers=5, n_values=2, max_term=2, max_log=0,
                  max_msgs=2, max_dup=1),
    spec="election",
    invariants=("NoTwoLeaders", "CommittedWithinLog"),
    symmetry=("Server",), chunk=4096)          # == runs/elect5_ddd.py

init_py = interp.init_state(CFG.bounds)
init_vec = interp.to_vec(init_py, CFG.bounds)
hi0, lo0 = sym_mod.init_fingerprint(CFG, init_py, init_vec)
digest = ckpt.config_digest(
    CFG, _DigestCaps(block=1 << 20, levels=1 << 12), (hi0, lo0))

schema = bitpack.BitSchema(CFG.bounds)
t0 = time.monotonic()
(rows_ls, con_ls, keystore, n_states, n_trans, cov, level_ends,
 blocks_done) = load_frontier_snapshot(CKPT, schema.P, digest)
wall = time.monotonic() - t0
print(json.dumps({
    "n_states": n_states, "n_trans": n_trans,
    "levels": len(level_ends), "blocks_done": blocks_done,
    "cur_span": [rows_ls.cur.base, len(rows_ls.cur)],
    "nxt_span": [rows_ls.nxt.base, len(rows_ls)],
    "wall_s": round(wall, 1)}))
rows_ls.close()
con_ls.close()
keystore.close()
